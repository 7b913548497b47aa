"""``benchmark/controls.py``: a reference's controls through the harness's own
comparison, on the CPU in float32 with the afmoe rehearsal's model (a sound
float32 run reads 3e-5, so what refuses a control here is the control)."""

import json
import os

import pytest

from benchmark import controls

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MODEL = "tiny-afmoe-top8"

# the limits each control must break, whatever else it breaks
MUST_BREAK = {
    "no-window": {"rel_err_median", "rel_err_decoded_median"},
    "rotary-on-full-layers": {"rel_err_median", "rel_err_decoded_median"},
    "another-token": {"rel_err_max", "rel_err_decoded_median"},
    "one-precision-lower": set(),   # at CI size: one median or the other
}


@pytest.fixture(scope="module")
def lines():
    import jax.numpy as jnp

    with open(os.path.join(REPO, "benchmark/configs/tiny-afmoe-rehearsal.json")) as f:
        config = json.load(f)
    out = []
    last = controls.read(config, MODEL, [5], dtype=jnp.float32, emit=out.append)
    assert out[-1] is last
    return out


def test_every_control_of_the_family_is_read(lines):
    assert set(MUST_BREAK) == set(controls.CONTROLS["afmoe"])
    assert [line["what"] for line in lines[:-1]] == ["sound", *MUST_BREAK]
    last = lines[-1]
    assert last["ok"] and last["sound_ok"] == [1, 1]
    assert last["controls_refused"] == {name: [1, 1] for name in MUST_BREAK}
    assert (last["reference"], last["seq_len"], last["decoded"]) == ("afmoe", 192, 48)


def test_the_sound_model_is_ok(lines):
    sound = lines[0]
    assert sound["ok"] and not sound["failed"]
    assert all(value < 1e-4 for value, _ in sound["compared"].values())


@pytest.mark.parametrize("name", MUST_BREAK)
def test_a_control_is_refused_by_a_limit_of_compared(lines, name):
    line = next(x for x in lines if x.get("what") == name)
    assert not line["ok"] and line["finite"] and line["stored_as_stated"]
    assert line["failed"] and MUST_BREAK[name] <= set(line["failed"])
    if name == "another-token":
        # another token's row is another row (the square root of 2 if the two
        # were unrelated): what TOLERANCE is held against
        assert 1.2 < line["compared"]["rel_err_max"][0] < 1.6
        assert line["compared"]["rel_err_median"][0] < 1e-4
