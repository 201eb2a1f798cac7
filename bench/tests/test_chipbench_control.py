"""The control, the plain reference at INT4 weights (the precision below
the configuration's INT8) put in the program's place on the same prompts
and served tokens, reads over the limit where the INT8 program reads
under it, through a whole run of each number's mix."""

import pytest

from chipbench_tiny import TINY_LIMITS, run


@pytest.mark.parametrize("mix,number", [("interactive-greedy", "logit_gap"),
                                        ("offline-beam4", "score_gap")])
@pytest.mark.parametrize("seed", [11, 2**31 + 7])
def test_int4_control_fails_where_int8_passes(mix, number, seed):
    out = run(mix, seed=seed, control_bits=4)
    assert out["correct"] is True
    limit = TINY_LIMITS["beam" if number == "score_gap" else "greedy"]
    assert out["compared"][number]["limit"] == limit[number]["limit"]
    assert out["checks"][f"control_{number}"] > limit[number]["limit"]
