"""Flash attention's share of its roofline: the least time the chip could
take for the causal flash calls of the traced steps (forward, dq and dkv of
every layer; operations and bytes from shapes, ``flops.flash_causal_call``)
over the summed device time of the step's custom calls in the trace. At
head size 64 and sequence 1,024 the bound is compute. Nothing to read where
the trace shows no custom call."""
from benchmark import flops

NAME, UNIT, SOURCE = "train_flash_roofline", "%", "device_trace"
LAYER, MOVES = "train kernels", "train_tokens_per_s"


def read(ctx):
    rec, cell, trace = ctx["records"], ctx["cell"], ctx["trace"]
    if trace.custom_call_s <= 0.0:
        return None
    cfg = cell.config
    call = flops.flash_causal_call(
        rec["global_batch"] // cell.chips, cfg["n_head"], rec["seq_len"],
        cfg["n_embd"] // cfg["n_head"])
    least = flops.roofline_seconds(call, flops.peaks(ctx["device_kind"]))
    return (100.0 * least["seconds"] * cfg["n_layer"] * len(rec["steps"])
            / trace.custom_call_s)
