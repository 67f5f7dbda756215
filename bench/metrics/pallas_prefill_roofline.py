"""The ``pallas_prefill`` kernel's share of its roofline: the least time
the chip could take for the suffix prefills of the window's prefix hits,
over the kernel's device time in the trace.

Work is counted from each chunk's real query tokens and the live prefix,
not from the bucket padding or the block table: a chunk of q tokens at
positions start .. start+q-1 does 4 * heads * head_dim * (q * start +
q * (q + 1) / 2) operations per layer and moves the K and V rows of its
start + q positions and the q query and output rows, in bf16."""
import flops
from readings import admissions, kernel_seconds, suffix_chunks

# the prefill kernel's instruction in the trace: a (b, s, kv_heads, group,
# head_dim) output from a custom call whose first operand is the block table
KERNEL = r"= bf16\[\d+,\d+,\d+,\d+,\d+\]\S* custom-call\(s32\[\d+,\d+\]"


def work(cfg: dict, start: int, q: int) -> tuple[int, int]:
    """(operations, bytes) of one layer's call for one chunk."""
    m = flops.dims(cfg)
    ops = 4 * m["h"] * m["hd"] * (q * start + q * (q + 1) // 2)
    moved = 2 * 2 * m["kv"] * m["hd"] * (start + q) + 2 * 2 * m["h"] * m["hd"] * q
    return ops, moved


def least_seconds(run, start: int, q: int) -> float:
    o, m = work(run.cfg, start, q)
    layers = flops.dims(run.cfg)["layers"]
    return layers * max(o / run.peak["bf16_flops_per_s"],
                        m / run.peak["hbm_bytes_per_s"])


def read(run):
    if not run.peak or run.trace is None:
        return None  # no chip peak, or no device trace
    spent = kernel_seconds(run, KERNEL)
    if not spent:
        return None
    least = sum(least_seconds(run, start, q)
                for _, _, _, hit, total in admissions(run) if hit
                for start, q in suffix_chunks(run, hit, total))
    return 100.0 * least / spent
