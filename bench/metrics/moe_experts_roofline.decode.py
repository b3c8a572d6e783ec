"""The grouped expert kernel's share of its roofline (`moe_experts`):
the least time the chip could take for every MoE layer of the window's
steps, from `counts/moe_experts.py` over each step's real tokens, over
the device time of the kernel's operations.  None where the model has
no experts or the trace holds no such kernel."""
from harness import xtrace


def read(run):
    d = run.dims
    if not getattr(d, "n_experts", 0):
        return None
    k = run.counts("moe_experts")
    need = 0.0
    for step in run.window.dispatches:
        tokens = sum(n for _, n in run.rows(step))
        ops, nbytes = k.call(tokens, d_model=d.d_model, d_expert=d.d_expert,
                             n_experts=d.n_experts, top_k=d.top_k)
        need += d.n_layers * k.roofline_s(ops, nbytes, run.peaks)
    spent = xtrace.kernel_s(run.trace, k.KERNEL)
    return 100.0 * need / spent if spent > 0 and need > 0 else None
