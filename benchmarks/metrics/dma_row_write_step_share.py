"""dma_row_write_step_share: see dma_row_write_step_share.json beside this file."""

from harness.window import family_sum


def read(run):
    dma = family_sum(run["counters"], "dmlc_fit_dma_row_write_steps_total")
    steps = family_sum(run["counters"], "dmlc_fit_steps_total")
    return dma / steps if dma is not None and steps else None
