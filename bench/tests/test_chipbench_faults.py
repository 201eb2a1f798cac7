"""With the timed path broken underneath, a whole run (all but the look
for a chip) comes out not correct: once for each fault a cell can have."""

import pytest

import chipbench_faults as faults
from chipbench_tiny import run

BEAM, GREEDY, REPLICAS = ("offline-beam4", "interactive-greedy",
                           "replicas4-offline-beam4")
CASES = [
    (BEAM, faults.state_unchanged),
    (BEAM, faults.half_batch),
    (BEAM, faults.token_altered),
    (GREEDY, faults.state_unchanged),
    (GREEDY, faults.half_batch),
    (GREEDY, faults.token_altered),
    (REPLICAS, faults.exchange_left_out),
]


@pytest.mark.parametrize("mix,fault", CASES,
                         ids=[f"{m}-{f.__name__}"
                              for m, f in CASES])
def test_fault_makes_the_run_incorrect(mix, fault):
    with fault():
        out = run(mix)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["compared"].values())
