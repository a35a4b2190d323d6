"""Share of the traced window (mean over the cell's chips) in which a
retrieval kernel ran on the chip: the operations of the IVF probe
(``ivf_scan``) and the fused PQ scan with its K-selection
(``chamvs_scan``), by the names the program gives them."""
import spans


def read(ctx):
    return spans.kernel_busy(ctx, ("chamvs_scan", "ivf_scan"))
