"""Helpers shared by the benchmark's CPU tests."""

from __future__ import annotations

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cpu_ok(chips: int) -> None:
    """The injected device check: accept the CPU."""


def last_json(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])
