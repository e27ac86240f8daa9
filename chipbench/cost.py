"""Operations and bytes of the fleet update, counted from the cell's shapes.

These are the least work any implementation of a DS-FD tick must do, so
the roofline share they give reads the same whatever implements it:

* bytes: every stream touched reads and writes its whole state once, and
  each absorbed row is read once: ``2 * state_bytes * streams + rows * d * 4``;
* flops: each absorbed row is projected on the sketch's ``ell``
  directions, the product every FD update needs: ``rows * 2 * d * ell``.

The state is two sketches (main and auxiliary) of the paper's layout: a
``(2 ell, d)`` float32 buffer, a ring of ``cap`` float32 snapshot vectors
with two int32 stamps and a validity byte each, and seven 4-byte scalars.
``cap = int(2 (1 + 4 / beta) / eps) + 4`` is the paper's bound on live
snapshots (Theorem 4.1, beta = 4) plus slack.
"""

from __future__ import annotations


def ell(system: dict) -> int:
    return int(min(max(round(1.0 / float(system["eps"])), 1),
                   int(system["d"])))


def ring_cap(system: dict, beta: float = 4.0) -> int:
    return int(2 * (1.0 + 4.0 / beta) / float(system["eps"])) + 4


def state_bytes(system: dict) -> int:
    """Bytes of one stream's DS-FD state (main + auxiliary sketch)."""
    d, m, cap = int(system["d"]), 2 * ell(system), ring_cap(system)
    one = 4 * m * d + 4 * cap * d + (4 + 4 + 1) * cap + 7 * 4
    return 2 * one


def update_cost(system: dict, rows: int, streams: int):
    """(flops, bytes) of one tick that absorbs ``rows`` rows over
    ``streams`` touched streams."""
    d = int(system["d"])
    flops = rows * 2 * d * ell(system)
    nbytes = 2 * state_bytes(system) * streams + rows * d * 4
    return flops, nbytes


def least_seconds(system: dict, rows: int, streams: int, peaks: dict):
    """The least time the chip could take: the larger of operations over
    the bf16 peak and bytes over the HBM peak; also which one bounds."""
    flops, nbytes = update_cost(system, rows, streams)
    t_flops = flops / float(peaks["bf16_flops_per_s"])
    t_bytes = nbytes / float(peaks["hbm_bytes_per_s"])
    return max(t_flops, t_bytes), ("bytes" if t_bytes >= t_flops
                                   else "flops")
