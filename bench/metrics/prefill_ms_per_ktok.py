"""Prefill time per thousand prompt tokens computed (not served from the
prefix cache): the ``request.prefill`` spans of the window's admissions."""
from readings import admissions


def read(run):
    adm = admissions(run)
    tokens = sum(total - hit for _, _, _, hit, total in adm)
    if not tokens:
        return None
    return sum(e - s for _, s, e, _, _ in adm) / tokens * 1e6
