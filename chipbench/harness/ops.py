"""Which device ops belong to which layer, by program and op.

Select is the top-k selection of the search program, by whatever op does
it: XLA's sort (the default route's two-key sort, and the sort that
merges the kernel route's candidates), XLA's top-k (``lax.top_k``: a
``topk`` op or the ``TopK`` custom call; ``lax.approx_max_k``: the
``PartialReduce`` custom call), and the qtopk Pallas kernel. In the trace
both Pallas kernels of the search program are custom calls with the target
``tpu_custom_call`` and no name of their own; the qtopk kernel returns a
tuple (values and ranks per block), the qgemm kernel one array of digit
planes. Scan is every other op of the search program.
"""

SEARCH_PROGRAMS = ("jit_exact_search",)
APPLY_PROGRAMS = ("jit__apply_insert_segment",)
SELECT_OPCODES = ("sort", "topk")
SELECT_TARGETS = ("TopK", "PartialReduce")


def in_search(op) -> bool:
    return op.module in SEARCH_PROGRAMS


def is_select(op) -> bool:
    return in_search(op) and (
        op.kind in SELECT_OPCODES or op.opcode in SELECT_OPCODES
        or op.target in SELECT_TARGETS
        or (op.target == "tpu_custom_call" and op.tuple_out))


def is_scan(op) -> bool:
    """Everything else the search program runs: the digit-plane splits and
    int8 dots (or the qgemm kernel), their int64 combine, norms, masking."""
    return in_search(op) and not is_select(op)


def in_apply(op) -> bool:
    """The insert loop of bulk apply: slot scatter and HNSW insert."""
    return op.module in APPLY_PROGRAMS
