"""The paged decode kernel's share of its roofline: the least time the
chip could take for the attention of every row decoded in the window,
over the kernel's device time in the trace.

Work is counted from each row's live context, not from the block table
the kernel walks: per layer and row of context length L, 4 * heads *
head_dim * L operations (scores and values) and the K and V rows of its
L positions plus the query read and the output written, in bf16."""
import flops
from readings import decode_ticks, kernel_seconds

# the decode kernel's instruction in the trace (no kernel name reaches it):
# a (b, kv_heads, group, head_dim) output from a custom call whose first
# operand is the (b, pages) block table
KERNEL = r"= bf16\[\d+,\d+,\d+,\d+\]\S* custom-call\(s32\[\d+,\d+\]"


def work(cfg: dict, length: int) -> tuple[int, int]:
    """(operations, bytes) of one layer's decode attention for one row."""
    m = flops.dims(cfg)
    ops = 4 * m["h"] * m["hd"] * length
    moved = 2 * 2 * m["kv"] * m["hd"] * length + 2 * 2 * m["h"] * m["hd"]
    return ops, moved


def least_seconds(run, rows: list[int]) -> float:
    """One decode call (all layers) over rows of these lengths."""
    f = b = 0
    for n in rows:
        o, m = work(run.cfg, n)
        f, b = f + o, b + m
    layers = flops.dims(run.cfg)["layers"]
    return layers * max(f / run.peak["bf16_flops_per_s"],
                        b / run.peak["hbm_bytes_per_s"])


def read(run):
    if not run.peak or run.trace is None:
        return None  # no chip peak, or no device trace
    spent = kernel_seconds(run, KERNEL)
    if not spent:
        return None
    return 100.0 * sum(least_seconds(run, rows)
                       for rows in decode_ticks(run)) / spent
