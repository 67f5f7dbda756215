"""The prefill step's share of the chip's peak: the model's operations for
the prompt tokens computed in the window's admissions over the
``request.prefill`` spans' time at the bf16 peak."""
from flops import prefill_flops
from readings import admissions


def read(run):
    if not run.peak:
        return None  # a device the peaks table does not hold
    adm = admissions(run)
    busy = sum(e - s for _, s, e, _, _ in adm)
    if not busy:
        return None
    ops = sum(prefill_flops(run.cfg, hit, total - hit)
              for _, _, _, hit, total in adm)
    return 100.0 * ops / (busy * run.peak["bf16_flops_per_s"] * run.chips)
