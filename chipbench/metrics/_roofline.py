"""Kernel roofline shares from the trace's kernel time and the work the
window needed (chipbench.work)."""
from chipbench import work


def paged_attention(run):
    t = run.kernel_s("paged_attention")
    lens = run.decode_lens()
    if t is None or not lens:
        return None
    f, b = work.paged_attention(run.cfg, lens)
    return work.roofline_share(f, b, t, run.peak)[0]


def page_move(run):
    t = run.kernel_s("page_move")
    pages = run.counter("blocks_moved")
    if t is None or not pages:
        return None
    f, b = work.page_move(run.cfg, pages, run.cfg["engine"]["page_T"])
    return work.roofline_share(f, b, t, run.peak)[0]


def mfu(run):
    t = run.trace
    if t is None or t["busy_s"] <= 0 or not run.steps:
        return None
    return 100.0 * run.model_flops() / (t["busy_s"]
                                        * run.peak["bf16_flops_per_s"])
