"""The harness: the command end to end at its rehearsal size, the form of
``BENCHMARK.json``, and that a cell, a configuration, a traffic mix and a
per-layer metric added as new files are found without editing any file."""

import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import common
from chipbench.generators.waves import Traffic, quantile_lengths
from tests.chipbench.conftest import REPO, ROOT

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = {w["name"]: w for w in BENCH["workloads"]}
END_TO_END = {m["name"]: m for m in BENCH["end_to_end"]}


def data(*parts):
    with open(os.path.join(ROOT, "chipbench", *parts)) as f:
        return json.load(f)


def reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def run_command(root, *argv, **env):
    return subprocess.run([sys.executable, os.path.join(root, "chipbench", "run.py"), *argv],
                          capture_output=True, text=True, timeout=900,
                          env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO, **env})


# ---------------------------------------------------------------- the command
@pytest.mark.parametrize("cell,trace", [(w["name"], str(i % 2))
                                        for i, w in enumerate(BENCH["workloads"])])
def test_rehearsal_walks_the_cell_and_is_never_a_result(rehearse, cell, trace):
    got = rehearse("--workload", cell, "--seed", str(2 ** 31 + 12345), "--seconds", "0",
                   "--trace", trace)
    assert got.code == 3
    assert '"correct": true' not in got.out
    assert got.line["correct"] is False and got.line["would_be_correct"] is True
    assert got.line["failed"] == 0 and got.line["attempted"] > 0
    mine = [m for m in (BENCH["per_layer"] if trace == "1" else BENCH["end_to_end"])
            if reports(m, cell)]
    if trace == "0":
        assert sorted(got.line["metrics"]) == sorted(m["name"] for m in mine)
        assert all(v["value"] > 0 for v in got.line["metrics"].values())
    else:  # a CPU trace has no accelerator plane: the counters are read, and they alone
        counters = {m["name"] for m in mine if m["source"] == "program_counter"}
        assert set(got.line["metrics"]) == counters


def test_without_a_chip_there_is_no_result():
    done = run_command(ROOT, "--workload", "serve.chat-burst", "--seed", "1", "--seconds", "1")
    assert done.returncode == 2
    assert "refused" in done.stderr and '"correct"' not in done.stdout


def test_unknown_workload_is_refused():
    done = run_command(ROOT, "--workload", "no.such-cell")
    assert done.returncode == 2 and '"correct"' not in done.stdout


# ------------------------------------------------------------ BENCHMARK.json
@pytest.mark.reads_benchmark
def test_top_level_form():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert "setup_s" in END_TO_END and END_TO_END["setup_s"]["bound"] <= 0.1
    assert all(0.01 <= m["bound"] <= 0.1 for m in BENCH["end_to_end"])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.reads_benchmark
def test_names_and_units_hold_only_what_the_driver_takes():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert set(m) <= {"name", "unit", "better", "bound", "source", "layer", "moves", "workloads"}
    assert all(m["source"] in ("host_clock", "device_trace") for m in BENCH["end_to_end"])
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"} and len(c["why"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.reads_benchmark
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_every_cell_resolves_to_files_that_exist(cell):
    w = CELLS[cell]
    config = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert config["file"] == f"chipbench/configs/{w['config']}.json"
    spec = data("configs", w["config"] + ".json")
    assert spec["source"] == config["source"]
    assert sorted(spec["reduced"]) == sorted(config["reduced"])
    traffic = data("traffic", w["traffic"] + ".json")
    for kind, name in (("entries", spec["entry"]), ("references", spec["reference"]),
                       ("generators", traffic["generator"])):
        assert os.path.exists(os.path.join(ROOT, "chipbench", kind, name + ".py")), (kind, name)
    mine = [m for m in BENCH["end_to_end"] if reports(m, cell)]
    assert "setup_s" in [m["name"] for m in mine] and len(mine) >= 2
    assert any(reports(m, cell) for m in BENCH["per_layer"])


# A key of ``reduced`` is a count.  The depth may always be cut; how many
# experts, heads or rows of the vocabulary this chip holds only as its share of
# a deployment that the file's ``deployment`` states, naming the key
# (model-configs guide, section 4).  Everything else is a width or a shape.
DEPTH = re.compile(r"^(num|n)_(hidden_)?layers?$")
SHARE = re.compile(r"^((num|n)_[a-z_]*(experts|heads|groups)|vocab_size)$")


@pytest.mark.reads_benchmark
@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_every_configuration_is_used_and_widths_are_published(config):
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    assert config in {w["config"] for w in BENCH["workloads"]}
    spec = data("configs", config + ".json")
    published = data("published", spec["published"] + ".json")
    assert spec["source"] == published["source"] == entry["source"]
    assert sorted(spec["reduced"]) == sorted(entry["reduced"])
    for key, value in published["config"].items():
        assert key in spec["reduced"] or (key in spec and spec[key] == value), key
    for key in spec["reduced"]:
        assert "per_tok" not in key and (DEPTH.match(key) or SHARE.match(key)), key
        assert DEPTH.match(key) or key in spec["deployment"], key
        assert isinstance(spec[key], int) and 0 < spec[key] < published["config"][key], key


@pytest.mark.reads_benchmark
@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
def test_every_metric_has_its_file_and_its_reader(metric):
    entry = next(m for m in BENCH["end_to_end"] + BENCH["per_layer"] if m["name"] == metric)
    spec = data("metrics", metric + ".json")
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec.get(key) == entry.get(key), key
    reader = importlib.import_module(f"chipbench.readers.{spec['reader']}")
    assert callable(reader.read)


@pytest.mark.reads_benchmark
@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_a_per_layer_metric_moves_a_metric_its_cells_report(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    moved = END_TO_END[entry["moves"]]
    cells = entry.get("workloads") or [c for c in CELLS if reports(moved, c)]
    assert cells and all(c in CELLS and reports(moved, c) for c in cells)
    if "roofline" in metric or "mfu" in metric:
        assert entry["unit"] == "%"
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all("\n" not in l and len(l) <= 200 for l in layers)


# ------------------------------------------------------------------- traffic
def test_same_seed_same_prompts_and_every_seed_the_same_work():
    params = data("traffic", "chat-burst.json")["params"]
    a, b, c = (Traffic(params, s, 32000) for s in (2 ** 31 + 7, 2 ** 31 + 7, 5))
    assert a.lengths == b.lengths and a.wave(3) == b.wave(3)
    assert a.wave(1) != a.wave(2) and a.wave(1) != c.wave(1)
    assert a.lengths == c.lengths  # the seed draws the tokens, never the work
    assert len(a.lengths) == 32 and 16 <= min(a.lengths) and 1024 < max(a.lengths) <= 2048
    assert [len(p) for p in a.wave(1)] == a.lengths
    lengths = quantile_lengths(data("traffic", "long-prompt.json")["params"]["prompt_lengths"], 8)
    assert sum(n > 4096 for n in lengths) == 2 and max(lengths) + 32 <= 40 * 128


@pytest.mark.parametrize("mix", ["chat-burst", "decode-heavy", "long-prompt"])
def test_the_order_of_a_wave_is_the_traffic_files_own(mix):
    params = data("traffic", mix + ".json")["params"]
    here, there = Traffic(params, 1, 32000), Traffic({**params, "order_seed": 1}, 1, 32000)
    assert sorted(here.lengths) == sorted(there.lengths) and here.lengths != there.lengths
    with pytest.raises(KeyError, match="order_seed"):  # a mix says which order ran
        Traffic({k: v for k, v in params.items() if k != "order_seed"}, 1, 32000)


@pytest.mark.reads_benchmark
@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]
                                  if data("configs", w["config"] + ".json")["entry"] == "serve"])
def test_the_pool_holds_what_each_wave_asks_for(cell):
    spec = data("configs", CELLS[cell]["config"] + ".json")
    mix = data("traffic", CELLS[cell]["traffic"] + ".json")
    engine = spec["engine"]
    wave = importlib.import_module(f"chipbench.generators.{mix['generator']}").Traffic(
        mix["params"], 1, spec["vocab_size"])
    blocks = [-(-(n + wave.max_new_tokens) // engine["block_size"]) for n in wave.lengths]
    assert max(blocks) <= engine["max_blocks_per_seq"]
    assert sum(blocks) < engine["num_blocks"]  # admitted whole; one block takes the padded writes


# ------------------------------------------- sizes are the published keys
TODAY = {"hidden_size": 4096, "intermediate_size": 14336, "num_attention_heads": 32,
         "num_key_value_heads": 8, "vocab_size": 32000, "max_position_embeddings": 32768,
         "rms_norm_eps": 1e-05, "rope_theta": 10000.0, "sliding_window": 4096}


@pytest.mark.parametrize("config,layers", [("mistral-7b-serve-16l", 16),
                                           ("mistral-7b-zero3-fsdp4", 10)])
def test_the_sizes_of_the_first_two_configurations_are_what_they_were(config, layers):
    spec = data("configs", config + ".json")
    assert common.published_sizes(spec, False) == {**TODAY, "num_hidden_layers": layers}
    assert common.published_sizes(spec, True) == {**TODAY, **spec["rehearsal"]["sizes"]}


def test_sizes_under_any_published_key_reach_the_programs_configuration(tmp_path, monkeypatch):
    # a sparse-expert model: its expert keys are in no list of this harness
    keys = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
            "num_key_value_heads": 2, "num_hidden_layers": 4, "vocab_size": 256,
            "num_local_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": False,
            "rope_scaling": None, "layer_types": ["full", "full"]}
    (tmp_path / "published").mkdir()
    (tmp_path / "published" / "sparse.json").write_text(json.dumps(
        {"source": "https://example.org/sparse", "config": {**keys, "num_hidden_layers": 32}}))
    monkeypatch.setattr(common, "HERE", str(tmp_path))
    spec = {**keys, "published": "sparse", "model_type": "sparse", "torch_dtype": "bfloat16",
            "rehearsal": {"sizes": {"num_hidden_layers": 1}},
            "program": {"model_module": "deepspeed_tpu.models.mixtral",
                        "config_class": "MixtralConfig",
                        "config_keys": {"num_local_experts": "num_experts",
                                        "num_experts_per_tok": "top_k",
                                        "hidden_size": "hidden_size",
                                        "num_hidden_layers": "num_layers"}}}
    sizes = common.published_sizes(spec, False)
    assert sizes == keys  # the published keys at the configuration's values, and no other key
    assert common.published_sizes(spec, True)["num_hidden_layers"] == 1
    module, model_cfg = common.program_model(spec, sizes, max_seq_len=512)
    assert module.__name__ == "deepspeed_tpu.models.mixtral"
    assert (model_cfg.num_experts, model_cfg.top_k) == (8, 2)
    assert (model_cfg.hidden_size, model_cfg.num_layers, model_cfg.max_seq_len) == (64, 4, 512)
    with pytest.raises(KeyError, match="num_local_experts"):  # a published key the file lacks
        common.published_sizes({k: v for k, v in spec.items() if k != "num_local_experts"}, False)


# ----------------------------------------------- new files, no file edited
def test_a_new_cell_config_mix_and_metric_are_found_without_an_edit(tmp_path):
    root = str(tmp_path / "copy")
    shutil.copytree(os.path.join(ROOT, "chipbench"), os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {p: open(os.path.join(dp, p), "rb").read()
              for dp, _, files in os.walk(os.path.join(root, "chipbench")) for p in files}
    bench = json.loads(json.dumps(BENCH))

    # another architecture: other widths, full attention and no window, a
    # published file of its own, another module of the program
    serving = next(c["name"] for c in BENCH["configs"]
                   if data("configs", c["name"] + ".json")["entry"] == "serve")
    config = data("configs", serving + ".json")
    other = {"hidden_size": 2048, "intermediate_size": 8192, "max_position_embeddings": 4096,
             "num_attention_heads": 16, "num_hidden_layers": 16, "num_key_value_heads": 16,
             "rms_norm_eps": 1e-06, "rope_theta": 500000.0, "vocab_size": 50304}
    source = "https://example.org/other-arch/config.json"
    theirs = data("published", config["published"] + ".json")["config"]
    config = {k: v for k, v in config.items() if k not in theirs}
    config.update(other, source=source, published="other-arch", num_hidden_layers=8)
    config["program"] = {"model_module": "deepspeed_tpu.models.llama", "config_class": "LlamaConfig",
                         "config_keys": {k: v for k, v in config["program"]["config_keys"].items()
                                         if k in other}}
    config["rehearsal"]["sizes"].update(num_hidden_layers=1, num_attention_heads=4,
                                        num_key_value_heads=4)
    mix = {"generator": "waves", "params": {"requests_per_wave": 16, "max_new_tokens": 32,
           "order_seed": 7, "prompt_lengths": {"dist": "uniform", "min": 64, "max": 128}}}
    metric = {"layer": "serve loop (engine_v2._serve_loop, fastpath.py)", "unit": "count",
              "better": "lower", "source": "program_counter", "moves": "serve_tok_s",
              "reader": "loop_iterations"}
    reader = 'def read(run):\n    return run.counters["loop_iterations"]\n'
    for path, text in (("published/other-arch.json", json.dumps({"source": source,
                                                                 "config": other})),
                       ("configs/new-config.json", json.dumps(config)),
                       ("traffic/new-mix.json", json.dumps(mix)),
                       ("metrics/serve.loop_iterations.json", json.dumps(metric)),
                       ("readers/loop_iterations.py", reader)):
        with open(os.path.join(root, "chipbench", path), "w") as f:
            f.write(text)
    bench["configs"].append({"name": "new-config", "source": config["source"], "why": "a test",
                             "file": "chipbench/configs/new-config.json",
                             "reduced": ["num_hidden_layers"]})
    bench["workloads"].append({"name": "serve.new-cell", "config": "new-config",
                               "traffic": "new-mix", "chips": 1, "why": "a test"})
    bench["per_layer"].append({"name": "serve.loop_iterations", "unit": "count",
                               "better": "lower", "source": "program_counter",
                               "layer": metric["layer"], "moves": "serve_tok_s",
                               "workloads": ["serve.new-cell"]})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tok_s":
            m["workloads"].append("serve.new-cell")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    done = run_command(root, "--workload", "serve.new-cell", "--seed", "3", "--seconds", "0",
                       "--trace", "1", "--rehearse")
    assert done.returncode == 3, done.stderr[-2000:]
    line = [l for l in done.stdout.splitlines() if l.startswith("[rehearsal-not-a-result] ")][-1]
    result = json.loads(line.split(" ", 1)[1])
    assert result["metrics"]["serve.loop_iterations"]["value"] > 0
    assert result["would_be_correct"] is True
    assert "requests_per_wave=4" in done.stdout and "layers=1" in done.stdout
    after = {p: open(os.path.join(dp, p), "rb").read()
             for dp, _, files in os.walk(os.path.join(root, "chipbench"))
             for p in files if "__pycache__" not in dp and os.sep + "out" not in dp}
    assert all(after[p] == before[p] for p in before if p in after)


def test_the_tests_take_a_cell_and_a_metric_without_an_edit(tmp_path):
    """What the test above proves of the harness, proved of the tests: a copy
    of the benchmark grown by an eighth cell (on the configuration of
    ``serve.chat-burst`` and a copy of its traffic file under another name) and
    by one per-layer entry after the last, then every ``reads_benchmark`` test
    of this directory over that copy (``CHIPBENCH_ROOT``), no file edited.  A
    test that finds a cell or a metric by its place, or counts its kind, fails
    here and not in the PR that brings the next model."""
    root = str(tmp_path / "copy")
    shutil.copytree(os.path.join(ROOT, "chipbench"), os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    like = CELLS["serve.chat-burst"]
    shutil.copy(os.path.join(root, "chipbench", "traffic", like["traffic"] + ".json"),
                os.path.join(root, "chipbench", "traffic", "guard-mix.json"))
    bench["workloads"].append({"name": "serve.guard-cell", "config": like["config"],
                               "traffic": "guard-mix", "chips": 1, "why": "a test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("serve_tok_s", "ttft_p95_ms"):
            m["workloads"].append("serve.guard-cell")
    # no list of cells: read wherever serve_tok_s is reported, the new cell among them
    layer = next(m["layer"] for m in BENCH["per_layer"] if m["name"] == "serve.host_syncs_per_tok")
    metric = {"name": "serve.guard_metric", "unit": "count", "better": "lower",
              "source": "program_counter", "layer": layer, "moves": "serve_tok_s"}
    bench["per_layer"].append(metric)
    for path, text in (("metrics/serve.guard_metric.json", json.dumps({**metric, "reader": "guard"})),
                       ("readers/guard.py", "def read(run):\n    return None\n")):
        with open(os.path.join(root, "chipbench", path), "w") as f:
            f.write(text)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    done = subprocess.run(
        [sys.executable, "-m", "pytest", os.path.join(REPO, "tests", "chipbench"), "-m",
         "reads_benchmark", "-v", "-p", "no:cacheprovider"], cwd=REPO, capture_output=True,
        text=True, timeout=120, env={**env, "JAX_PLATFORMS": "cpu", "CHIPBENCH_ROOT": root})
    assert done.returncode == 0, done.stdout[-4000:] + done.stderr[-2000:]
    passed = [l.split(" ")[0].split("::")[1] for l in done.stdout.splitlines() if " PASSED" in l]
    for test in ("test_every_cell_resolves_to_files_that_exist[serve.guard-cell]",
                 "test_the_pool_holds_what_each_wave_asks_for[serve.guard-cell]",
                 "test_every_metric_has_its_file_and_its_reader[serve.guard_metric]",
                 "test_a_per_layer_metric_moves_a_metric_its_cells_report[serve.guard_metric]",
                 "test_the_metric_file_and_the_benchmarks_entry_agree",  # the two that pinned it
                 "test_the_metric_files_make_entries_the_benchmark_can_take",
                 "test_the_borrowed_readers_are_right_for_this_cell_and_the_others_are_not"):
        assert test in passed, test  # the copy was the root, and the new entries were walked
