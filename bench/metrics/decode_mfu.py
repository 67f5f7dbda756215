"""The decode step's share of the chip's peak: the model's operations for
every token decoded in the window (its products, attention over the
row's live context, one row of logits) over the decode steps' time at
the bf16 peak."""
from flops import decode_flops
from readings import decode_ticks, spans_in_window


def read(run):
    if not run.peak:
        return None  # a device the peaks table does not hold
    busy = sum(e - s for s, e in spans_in_window(run, "decode.tick"))
    if not busy:
        return None
    ops = sum(decode_flops(run.cfg, n) for rows in decode_ticks(run)
              for n in rows)
    return 100.0 * ops / (busy * run.peak["bf16_flops_per_s"] * run.chips)
