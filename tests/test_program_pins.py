"""Every served family's programs lower to the text recorded for them, and its
cache specification and program key are the recorded ones
(tests/data/program_pins.json; tests/programs.py says what the pins are for
and re-records them).  A change to shared code that is meant to leave a
family's programs alone leaves its four texts byte for byte what they were."""
import pytest

import jax

import programs

RECORDED = programs.recorded()
FAMILIES = sorted(RECORDED["texts"])


@pytest.fixture(scope="module")
def pins_of():
    """family -> {kind: pin}, one engine a family, its four programs lowered
    at first asking."""
    kept = {}

    def of(family):
        if family not in kept:
            texts = programs.lowered(programs.pinned_engine(family), debug_info=False)
            kept[family] = {k: programs.text_pin(t) for k, t in texts.items()}
        return kept[family]
    return of


@pytest.mark.skipif(jax.__version__ != RECORDED["jax"],
                    reason="the texts are jax " + RECORDED["jax"] + "'s")
@pytest.mark.parametrize("kind", programs.KINDS)
@pytest.mark.parametrize("family", FAMILIES)
def test_a_familys_program_lowers_to_the_recorded_text(pins_of, family, kind):
    assert pins_of(family)[kind] == RECORDED["texts"][family][kind]


@pytest.mark.parametrize("family", FAMILIES)
def test_a_familys_cache_spec_and_program_key_are_the_recorded_ones(family):
    assert programs.spec_pin(programs.pinned_model(family)) == RECORDED["specs"][family]
