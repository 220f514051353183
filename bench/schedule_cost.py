"""Work the cadence scheduler's kernel must do, reckoned from shapes
(``src/repro/kernels/cadence_schedule.py``), beside ``cost.py``.

The kernel reads each op once, packed into one int32 (session, replica,
write flag), and writes each op's apply point once, an int32.  Its work
is a serial chain of small vector steps per merge period on state that
never leaves the chip, so no operation count of it has a published
peak: its roofline is the memory's.
"""

from __future__ import annotations

INT32 = 4


def schedule_bytes(n_ops: int) -> int:
    """HBM bytes one scheduling of ``n_ops`` ops must move."""
    return 2 * INT32 * n_ops
