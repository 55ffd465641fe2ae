"""One flash kernel's share of its roofline, for the readers that tell the
forward from the backward: the kernels carry names of their own on the
trace's ``XLA Ops`` line (``flash_fwd.N``, ``flash_dq.N``, ``flash_dkv.N``,
the ``name=`` of each ``pl.pallas_call``). One reading of all three
together (``train_flash_roofline``, every custom call of the step summed)
went with PR 31: the pair says more."""

from benchmark import flops

PRODUCTS, PASSES = 7, 12    # of ``flops.flash_causal_call``: forward 2 + 4,
                            # backward (dq and dkv kernels together) 5 + 8


def roofline_share(ctx, kernels, products, passes):
    """The least time the chip could take for ``products`` of the call's
    matrix products and ``passes`` of its tensor passes, over the summed
    device time of the operations named ``<kernel>`` or ``<kernel>.N``, in
    percent; nothing where the trace shows no such operation."""
    rec, cell, trace = ctx["records"], ctx["cell"], ctx["trace"]
    taken = sum(s for name, s in trace.ops.items()
                if name.split(".")[0] in kernels)
    if taken <= 0.0:
        return None
    cfg = cell.config
    call = flops.flash_causal_call(
        rec["global_batch"] // cell.chips, cfg["n_head"], rec["seq_len"],
        cfg["n_embd"] // cfg["n_head"])
    part = {"flops": call["flops"] * products / PRODUCTS,
            "bytes": call["bytes"] * passes / PASSES}
    least = flops.roofline_seconds(part, flops.peaks(ctx["device_kind"]))
    return (100.0 * least["seconds"] * cfg["n_layer"] * len(rec["steps"])
            / taken)
