"""What a run prints besides its result, and how compared numbers meet their
limits: shared by the harness and by both checks."""

from __future__ import annotations

import json


def say(**line):
    """An earlier line of the run: observations, never the result."""
    print(json.dumps(line), flush=True)


def limited(readings: dict, limits: dict) -> dict:
    """{name: [value, limit]} for the readings that the traffic file gives a
    limit (a control's `control_<kind>_<name>` takes <name>'s); the others are
    printed on an earlier line and not compared."""
    base = lambda k: k.split("_", 2)[-1] if k.startswith("control") else k
    return {k: [v, limits[base(k)]] for k, v in readings.items()
            if base(k) in limits}
