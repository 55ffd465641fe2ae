"""Device-idle time inside the traced window that falls under the runner's
host spans (queue top-up, ``step()``: scheduling, table building, delivery;
``collect()``), over the window."""
NAME, UNIT, SOURCE = "offline_host_gap_share", "%", "device_trace"
LAYER, MOVES = "serve engine", "serve_tokens_per_s"


def read(ctx):
    trace = ctx["trace"]
    under = sum(s for name, s in trace.idle_under.items()
                if name != "outside-spans")
    return 100.0 * under / trace.window_s
