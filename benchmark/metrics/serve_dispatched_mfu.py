"""The whole serving step's share of the chip's bf16 peak, counted where
the work is dispatched: required operations of the prefill chunks and the
decode rows that the program's own ``serving_prefill`` and
``serving_decode`` spans send out inside the traced window, over the
window and the peak. With ``f`` the cell's count of a request's operations
(``serve_flops`` of its reference where it has one, else
``flops_mla_moe``'s for a latent configuration, else ``flops``'s), a chunk
of positions [s, s + n) whose first r tokens are re-prefilled after a
preemption is charged f(s + n, 0, 1) - f(s + r, 0, 1), and a decode row
whose input sits at position t f(t, 1, 2). Over a request served whole the
charges sum to f(p, 0, o), what the readers that charge delivered tokens
count, but each lands where the device was handed the work, not where a
window's edge finds a first token. f(t, 1, 2) is linear in t, so a decode
span's ``rows`` and ``positions`` are enough. Nothing to read where the
spans lack those arguments (a program before them), or under speculation,
where a row costs a draft and a verify."""
from benchmark import flops, flops_mla_moe, harness, program_spans

NAME, UNIT, SOURCE = "serve_dispatched_mfu", "%", "program_span"
LAYER, MOVES = "serve step", "serve_tokens_per_s"


def count_of(config):
    """The cell's count of a request's required operations, unedited."""
    ref = harness.load_named("reference", config["reference"])
    if hasattr(ref, "serve_flops"):
        return ref.serve_flops
    if "kv_lora_rank" in config:
        return flops_mla_moe.serve_flops
    return flops.serve_flops


def dispatched_flops(config, prefills, decodes):
    """Required operations of some ``serving_prefill`` and ``serving_decode``
    spans; None where one lacks an argument it is charged from."""
    if any("recompute" not in s.args for s in prefills) or any(
            "rows" not in s.args or "positions" not in s.args
            for s in decodes):
        return None
    f = count_of(config)
    need = 0.0
    for s in prefills:
        a = s.args["start"] + s.args["recompute"]
        b = s.args["start"] + s.args["tokens"]
        if b > a:
            need += f(config, b, 0, 1) - f(config, a, 0, 1)
    row = f(config, 0, 1, 2)
    per_position = f(config, 1, 1, 2) - row
    for s in decodes:
        need += s.args["rows"] * row + s.args["positions"] * per_position
    return need


def read(ctx):
    cell = ctx["cell"]
    serving = {**cell.config["deployment"]["serving"],
               **cell.traffic.get("serving", {})}
    decodes = program_spans.named(ctx, "serving_decode")
    if not decodes or (serving.get("speculative") or {}).get("enabled"):
        return None
    need = dispatched_flops(cell.config,
                            program_spans.named(ctx, "serving_prefill"),
                            decodes)
    if need is None:
        return None
    peak = flops.peaks(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * need / ctx["records"]["window_s"] / (cell.chips * peak)
