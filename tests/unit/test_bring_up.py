"""What PR 21's bring-up on the chip rests on, as far as the CPU can hold it:
no backend at import, a compile cache placed from outside, one table of peaks
with no default, Mistral's training attention on the default path while the
sequence fits the window, the flash kernel per shard under a mesh, and a
``chip_smoke.py`` that never says ok off the chip."""

import importlib.util
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ------------------------------------------------------- one process per chip
def test_imports_initialise_no_backend():
    """A parent that touches a device holds the chip; the modules whose
    processes spawn workers must stay off it at import."""
    code = (
        "import deepspeed_tpu\n"
        "import deepspeed_tpu.inference.v2.engine_v2, deepspeed_tpu.inference.v2.supervisor\n"
        "import deepspeed_tpu.elasticity.elastic_agent, deepspeed_tpu.launcher.runner\n"
        "import deepspeed_tpu.utils.compile_cache, deepspeed_tpu.accelerator.device_peaks\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, list(xla_bridge._backends)\n"
        "print('no backend')\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0 and "no backend" in r.stdout, r.stderr[-2000:]


# -------------------------------------------------------------- compile cache
@pytest.fixture
def cache_updates(monkeypatch):
    """Record what the helper would set instead of re-pointing this process."""
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_env_set_means_nothing_is_set_in_code(monkeypatch, cache_updates, tmp_path):
    from deepspeed_tpu.utils.compile_cache import place_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "elsewhere"))
    assert place_compile_cache(str(tmp_path)) is None
    assert cache_updates == []
    assert not os.path.exists(tmp_path / ".jax_cache")


def test_compile_cache_unset_means_fixed_path_in_the_checkout(monkeypatch, cache_updates,
                                                              tmp_path):
    from deepspeed_tpu.utils.compile_cache import place_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(str(tmp_path), ".jax_cache")
    assert place_compile_cache(str(tmp_path)) == want
    assert place_compile_cache(str(tmp_path)) == want  # fixed: no pid, no time
    assert cache_updates == [("jax_compilation_cache_dir", want)] * 2


def test_no_literal_cache_dir_outside_the_helper():
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".") and d not in ("tests", "chiprun_out")]
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path) as fh:
                    if "jax_compilation_cache_dir\"," in fh.read().replace("'", '"'):
                        hits.append(os.path.relpath(path, REPO))
    assert hits == ["deepspeed_tpu/utils/compile_cache.py"]


# ------------------------------------------------------------- one instrument
# Names of what PR 48 retired (the pre-chip harness, its gate, its records, two
# orphans), each in parts so that this file does not hold them.
RETIRED = {
    "harness-import": r"import " + r"bench\b",
    "gate-package": "bench" + "track",
    "gate-cli": "dstpu-" + "benchdiff",
    "scripts-dir": "bench" + "marks/",
    "lanes-record": "TESTS_" + "LANES",
    "infinity-script": "run_infinity" + "_7b",
    "hessian-module": "eigen" + "value",
    "offload-dead-keys": "pipeline" + "_read",
}


@pytest.fixture(scope="module")
def live_lines():
    """What speaks for the tree as it is, read once: every ``*.py``, the
    Makefile, pytest.ini, the root's ``*.json``, the README and the verify
    notes.  The records that tell history (CHANGES, ROADMAP, PERF, ISSUE, ...)
    are not among them."""
    paths = [os.path.join(REPO, name) for name in sorted(os.listdir(REPO))
             if name in ("Makefile", "pytest.ini", "README.md") or name.endswith(".json")]
    paths.append(os.path.join(REPO, ".claude", "skills", "verify", "SKILL.md"))
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".") and d not in ("chiprun_out",
                                                                          "__pycache__")]
        paths += [os.path.join(root, f) for f in sorted(files) if f.endswith(".py")]
    lines = []
    for path in paths:
        with open(path, encoding="utf-8") as fh:
            lines += [(f"{os.path.relpath(path, REPO)}:{i}", line) for i, line in enumerate(fh, 1)]
    return lines


@pytest.mark.parametrize("name", sorted(RETIRED))
def test_nothing_live_names_a_retired_file(name, live_lines):
    pattern = re.compile(RETIRED[name])
    assert [where for where, line in live_lines if pattern.search(line)] == []


# ----------------------------------------------------------------- peak table
def test_peaks_known_kind():
    from deepspeed_tpu.accelerator.device_peaks import device_peaks
    v5e = device_peaks("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bytes_per_s) == (197e12, 819e9)


@pytest.mark.parametrize("kind", ["cpu", "TPU v9", ""])
def test_peaks_unknown_kind_is_an_error(kind):
    from deepspeed_tpu.accelerator.device_peaks import UnknownDeviceError, device_peaks
    with pytest.raises(UnknownDeviceError, match="no published peaks"):
        device_peaks(kind)


def test_unknown_device_has_no_mfu_peak():
    """Telemetry's MFU is null, the table's lookup raises: never the v5e figure."""
    from deepspeed_tpu.accelerator.device_peaks import UnknownDeviceError, device_peaks
    from deepspeed_tpu.monitor.telemetry import detect_peak_flops_per_chip
    assert detect_peak_flops_per_chip() is None
    with pytest.raises(UnknownDeviceError):
        device_peaks(jax.devices()[0].device_kind)


def test_autotuner_refuses_to_guess_device_memory():
    from deepspeed_tpu.autotuning.autotuner import Autotuner, ModelInfo
    info = ModelInfo(num_params=1_000_000, activation_mem_per_mbs=1 << 20)
    with pytest.raises(ValueError, match="reports no device memory"):
        Autotuner(info, runner=lambda e: None, dp_size=1)


# ------------------------------------------------------------------- mistral
def _qkv(seq, heads=4, kv_heads=2, dh=16, batch=2, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((batch, seq, h, dh)), jnp.float32)
                 for h in (heads, kv_heads, kv_heads))


@pytest.mark.parametrize("seq,window", [(16, 16), (12, 16), (24, 16)],
                         ids=["seq==window", "seq<window", "seq>window"])
def test_mistral_training_attention_equals_dense_window(seq, window):
    from deepspeed_tpu.models import mistral
    q, k, v = _qkv(seq)
    got = mistral.windowed_attention(window)(q, k, v)
    want = mistral.dense_windowed_attention(window)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_mistral_routes_to_default_attention_while_the_window_holds(monkeypatch):
    from deepspeed_tpu.models import mistral
    seen = []

    def fake_default():
        def attn(q, k, v, causal=True, mask=None, softmax_scale=None):
            seen.append((causal, mask))
            return q
        return attn

    monkeypatch.setattr(mistral, "default_attention", fake_default)
    attn = mistral.windowed_attention(16)
    attn(*_qkv(16))
    assert seen == [(True, None)]
    attn(*_qkv(17))  # past the window: the dense mask, not the default path
    assert len(seen) == 1


def test_init_linear_keeps_the_dtype_it_was_asked_for():
    """A numpy scalar for the scale promoted bf16 weights to float32: twice
    the bytes of a 7B-width model, found by the chip's memory analysis."""
    from deepspeed_tpu.models import mistral
    cfg = mistral.MistralConfig.tiny()
    shapes = mistral.abstract_params(cfg, dtype=jnp.bfloat16)
    assert {x.dtype for x in jax.tree_util.tree_leaves(shapes)} == {jnp.dtype(jnp.bfloat16)}


# ------------------------------------------------------- kernels under a mesh
def test_flash_runs_per_shard_under_the_engines_mesh(monkeypatch):
    """GSPMD cannot partition a Mosaic kernel; under an installed multi-device
    topology the call is wrapped in shard_map over batch (and heads)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.models.transformer import sdpa
    from deepspeed_tpu.ops import _pallas
    from deepspeed_tpu.ops.attention.flash import flash_attention
    from deepspeed_tpu.parallel import MeshTopology, set_topology
    monkeypatch.setattr(_pallas, "INTERPRET", True)
    q, k, v = _qkv(128, heads=4, kv_heads=2, dh=32, batch=4)
    want = np.asarray(sdpa(q, k, v, causal=True))
    plain = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    assert "shard_map" not in str(jax.make_jaxpr(plain)(q, k, v))  # no topology installed
    np.testing.assert_allclose(np.asarray(plain(q, k, v)), want, rtol=2e-3, atol=2e-3)

    topo = MeshTopology.from_axis_dict({"fsdp": 2, "tensor": 2}, devices=jax.devices()[:4])
    set_topology(topo)
    sharded = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    assert "shard_map" in str(jax.make_jaxpr(sharded)(q, k, v))
    spec = NamedSharding(topo.mesh, P("fsdp", None, "tensor", None))
    out = sharded(*(jax.device_put(x, spec) for x in (q, k, v)))
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-3, atol=2e-3)


def test_kernel_calls_reads_lowered_and_compiled_text():
    from deepspeed_tpu.ops._pallas import kernel_calls
    text = "\n".join([
        '%2 = stablehlo.custom_call @tpu_custom_call(%a) {backend_config = "x", '
        'kernel_name = "paged_attention", other = 1}',
        '%f = bf16[4] custom-call(%q), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(step)/while/body/flash_attention_fwd/pallas_call" id=5}',
        '%g = bf16[4] custom-call(%q), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(loss)/transpose(jvp(flash_attention_bwd_dq))/pallas_call"}',
        '%h = f32[4] custom-call(%q), custom_call_target="tpu_custom_call"',
        '%i = f32[4] custom-call(%q), custom_call_target="Sharding"',
    ])
    assert kernel_calls(text) == {"paged_attention": 1, "flash_attention_fwd": 1,
                                  "flash_attention_bwd_dq": 1, "unnamed": 1}


@pytest.mark.parametrize("n,maxb,fits", [
    (512, 512, False), (512, 384, True), (1000, 256, True), (1000, 260, False),
    (32, 8000, True), (32, 8100, False), (128, 2048, False), (32, 40, True),
])
def test_block_table_bound_is_the_compilers(n, maxb, fits):
    """Each row is what the v5e compiler answered for that table (PR 21)."""
    from deepspeed_tpu.ops.attention.paged import check_block_table_fits
    if fits:
        check_block_table_fits(n, maxb)
    else:
        with pytest.raises(ValueError, match="scalar memory"):
            check_block_table_fits(n, maxb)


# ----------------------------------------------------------------- chip_smoke
def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  os.path.join(REPO, "chip_smoke.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_chip_smoke_refuses_on_cpu():
    """As the driver runs it, in a sandbox without an accelerator: non-zero,
    before any phase, and no result line."""
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
                       capture_output=True, text=True, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 2, (r.returncode, r.stderr[-2000:])
    assert '"ok"' not in r.stdout and "[serve]" not in r.stdout
    assert "no TPU" in r.stderr


@pytest.fixture
def smoke(monkeypatch):
    from deepspeed_tpu.ops import _pallas
    monkeypatch.setattr(_pallas, "INTERPRET", False)  # main() flips it; restored here
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py", "--rehearse"])
    return _load_chip_smoke()


def test_chip_smoke_rehearsal_never_says_ok(smoke, monkeypatch, capsys):
    monkeypatch.setattr(smoke, "serve_phase", lambda *a: None)
    monkeypatch.setattr(smoke, "train_phase", lambda *a: None)
    assert smoke.main() == 3
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_phase_that_raises_ends_the_run(smoke, monkeypatch, capsys):
    def boom(*a):
        raise RuntimeError("phase failed")

    monkeypatch.setattr(smoke, "serve_phase", lambda *a: None)
    monkeypatch.setattr(smoke, "train_phase", boom)
    with pytest.raises(RuntimeError, match="phase failed"):
        smoke.main()  # nothing catches it: the process exits non-zero
    assert '"ok"' not in capsys.readouterr().out
