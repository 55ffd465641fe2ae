"""The harness at a size a test run can hold: the program's ``tiny`` shapes
(with a vocabulary large enough for near-tied logits), driven in-process on
the CPU through the very functions a chip run drives, minus the look for a
chip. A CPU run yields counts and comparisons, never a speed."""

import json
from pathlib import Path

from benchmark import harness

TINY = json.loads(Path(__file__).with_name("tiny.json").read_text())


def cell(traffic_name):
    """The test-size configuration under one of ``tiny.json``'s traffic
    mixes (each is what a file of ``benchmark/traffic/`` holds)."""
    traffic = TINY["traffic"][traffic_name]
    train = traffic["kind"] == "train"
    rate = "train_tokens_per_s" if train else "serve_tokens_per_s"
    return harness.Cell(
        name=f"tiny.{traffic_name}", config=TINY["config"], traffic=traffic,
        chips=int(traffic["chips"]),
        end_to_end=({"name": rate, "unit": "tokens/s"},
                    {"name": "setup_s", "unit": "s"}),
        per_layer=(), limits=TINY["limits"]["train" if train else "serve"])
