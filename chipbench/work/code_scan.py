"""Work of the coarse route's code scan, from shapes: what the algorithm
needs, not what an implementation does (digit planes and partial sums earn
no credit)."""


def coarse(nq: int, live: int, dim: int) -> tuple:
    """(integer ops, bytes) to score nq queries against the int8 codes of
    live rows: one multiply-add per (query, row, dimension); the codes read
    once at 1 B a value, each row's int64 norm once, and the int32 query
    weights once."""
    return 2 * nq * live * dim, live * dim + 8 * live + 4 * nq * dim


def bound_s(nq: int, live: int, dim: int, peaks: dict) -> float:
    """The least time the chip could take: the larger of ops over the int8
    peak and bytes over HBM bandwidth."""
    ops, nbytes = coarse(nq, live, dim)
    return max(ops / peaks["int8_ops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
