"""Bit-identity gate: legacy wrappers vs their pre-unification outputs.

``tests/data/golden_wrappers.json`` holds the sanitized results of 30
representative driver invocations captured on the commit *before* the
``run_protocol_*`` twins were folded into the unified epoch engine
(``repro.engine``) — all six consistency levels through each driver,
plus gossip/recovery, outage, sharded-faulty, and adaptive composites.
Each test replays one case through today's wrapper and requires the
sanitized result to match: every integer exactly, every float to
``golden_bridge.RTOL`` (float32 sums reorder between XLA builds and
backends).  The engine refactor is a pure reorganization, and any
drift in a count is a bug.

The golden file is an artifact, not derived state — regenerating it
against current code would turn this gate into a tautology.  It should
only ever be re-captured on a commit whose outputs are independently
trusted (see ``tests/golden_bridge.py``).
"""

import pytest

import golden_bridge


GOLDEN = golden_bridge.load_golden()


@pytest.mark.parametrize("name", golden_bridge.case_names())
def test_wrapper_bit_identical(name):
    assert name in GOLDEN, (
        f"case {name!r} missing from golden_wrappers.json — re-capture "
        "on a trusted commit via tests/golden_bridge.py"
    )
    got = golden_bridge.run_case(name)
    assert golden_bridge.match(got, GOLDEN[name]) == []
