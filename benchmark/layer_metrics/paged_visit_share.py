"""Paged attention core: the pages the kernel walks over the pages the
lanes' block tables span, in the window's decode waves (the program's
counters `paged_pages_visited` / `paged_pages_spanned`: for every lane
of every staged wave, `hi - lo` of `attended_pages` at its position
over `nblk`). Lower is better: a page not walked costs no grid step."""
from .. import readers

LAYER, SOURCE = "paged_attention_core", "program_counter"
VISITED, SPANNED = "paged_pages_visited", "paged_pages_spanned"


def read(ctx):
    obs = ctx["obs"]
    a, b = obs.get("snap0"), obs.get("snap1")
    if not a or not b or not all(k in s for s in (a, b)
                                 for k in (VISITED, SPANNED)):
        return None
    return readers.percent(b[VISITED] - a[VISITED], b[SPANNED] - a[SPANNED])
