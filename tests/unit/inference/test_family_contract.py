"""The guard of ``family_contract.py``: a family's serving file is a ``Family``
value and a subclass of the contract, so that an eighth copy without it shows in
review.  No model runs here: a ``Family`` is plain data, and importing a
family's test module computes nothing."""

import importlib
import pathlib
import re

import pytest

from tests.unit.inference.family_contract import Family, ServingContract, StatefulContract, WrongReadings

HERE = pathlib.Path(__file__).parent
FAMILIES = ["test_lfm2_state", "test_qwen3_next_state", "test_glm_moe_dsa", "test_longcat_flash",
            "test_granite_moe_hybrid", "test_afmoe", "test_bailing_hybrid", "test_nemotron_h"]


@pytest.mark.parametrize("name", FAMILIES)
def test_a_familys_file_states_a_family_and_takes_the_contracts_cases(name):
    module = importlib.import_module(f"tests.unit.inference.{name}")
    family = module.FAMILY
    assert isinstance(family, Family)
    reference = pathlib.Path(family.reference.__file__)
    assert reference.parent.name == "references" and reference.parent.parent.name == "chipbench"
    assert reference.stem == family.module.__name__.rsplit(".", 1)[-1]  # the plain twin of the program
    assert 0 < family.tolerance <= 3e-4 and len(family.tolerance_reason.split()) >= 12  # a reason, in words
    assert bool(family.state_leaves) == (family.pool.slots is not None)
    classes = [c for c in vars(module).values() if isinstance(c, type) and c.__module__ == module.__name__
               and issubclass(c, ServingContract)]
    assert len(classes) == 1 and classes[0].family is family and classes[0].__name__.startswith("Test")
    # nothing of the contract is spelt again: a shared case overridden would be a copy
    shared = {n for c in (ServingContract, StatefulContract, WrongReadings) for n in vars(c) if n.startswith("test_")}
    assert not shared & set(vars(classes[0]))
    source = (HERE / f"{name}.py").read_text()
    assert not re.search(r"InferenceEngineV2\(|^def (step|greedy|fresh_cache|ids_of|want|close)\(", source, re.M)
    assert re.search(r"builds (two|three) engine\s+configurations", module.__doc__)


def test_every_file_that_holds_a_program_against_its_reference_is_one_of_the_families():
    held = sorted(path.stem for path in HERE.glob("test_*.py")
                  if re.search(r"^from chipbench\.references import", path.read_text(), re.M))
    assert held == sorted(FAMILIES)
