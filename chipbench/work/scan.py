"""Work of the exact scan, from shapes: what the algorithm needs, not what
an implementation does (digit planes and partial sums earn no credit)."""


def exact(nq: int, live: int, dim: int) -> tuple:
    """(integer ops, bytes) to score nq queries against live rows: one
    multiply-add per (query, row, dimension), the rows read once at 4 B a
    value (Q16.16 int32) and the queries once."""
    return 2 * nq * live * dim, 4 * (live + nq) * dim


def bound_s(nq: int, live: int, dim: int, peaks: dict) -> float:
    """The least time the chip could take: the larger of ops over the int8
    peak and bytes over HBM bandwidth."""
    ops, nbytes = exact(nq, live, dim)
    return max(ops / peaks["int8_ops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
