"""The paged-attention kernel's share of its roofline in a model whose
layers mix sliding-window and full attention: the least time for every
layer of the window's steps, each sliding layer's positions and pages
capped at its window (`counts/paged_attention_windowed.py`, the
reference's `windows`), over the device time of the kernel's
operations.  None where the reference names no windows."""
from harness import xtrace


def read(run):
    windows = getattr(run.reference, "windows", None)
    if windows is None:
        return None
    k = run.counts("paged_attention_windowed")
    d, eng = run.dims, run.engine
    pps = -(-eng["max_len"] // eng["page_size"])
    per_layer = windows(d)
    need = 0.0
    for step in run.window.dispatches:
        rows = run.rows(step)
        for w in per_layer:
            ops, nbytes = k.call(
                rows, n_heads=d.n_heads, n_kv_heads=d.n_kv_heads,
                head_dim=d.head_dim, page_size=eng["page_size"],
                pages_per_slot=pps, kv_bits=eng["kv_bits"], window=w)
            need += k.roofline_s(ops, nbytes, run.peaks)
    spent = xtrace.kernel_s(run.trace, k.KERNEL)
    return 100.0 * need / spent if spent > 0 and need > 0 else None
