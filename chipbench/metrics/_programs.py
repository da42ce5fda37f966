"""Shared arithmetic of the readers: the programs of a reduced trace,
found by a word of their name (``decode``, ``prefill``, ``planned_sync``,
``xla_sync``)."""
from __future__ import annotations

from typing import Dict, Optional


def program(trace: Dict, word: str) -> Optional[Dict[str, float]]:
    """Runs and device seconds of the traced programs whose name holds
    ``word``, or None if none ran."""
    mods = [v for k, v in trace["modules"].items() if word in k]
    count = sum(m["count"] for m in mods)
    if not count:
        return None
    return {"count": count, "seconds": sum(m["seconds"] for m in mods)}
