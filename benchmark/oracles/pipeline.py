"""imaginary's /pipeline: the operations of the `operations` JSON list,
applied in order, each by its own oracle; `convert` changes only the
output's coding."""

from __future__ import annotations

import json


def expected(src, q: dict, ops):
    x = src  # a Source for the first operation, a frame after it
    for step in json.loads(q["operations"]):
        name = step["operation"]
        if name == "convert":
            continue
        x = ops.oracle(name)(x, {k: v for k, v in step.get("params", {}).items()}, ops)
    return x
