"""Trace-time program records: what a traced call does, by call shape.

A layer records its numbers as it is built, when jax traces it, never per
execution: a compiled step that runs a thousand times records once. The
benchmark's per-layer metrics read the records after a traced window.
"""

from __future__ import annotations

import threading
from typing import Dict, Hashable


class TraceRecord:
    """Numbers by call shape, shared by the threads that trace."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._items: Dict[Hashable, Dict[str, int]] = {}

    def put(self, call: Hashable, numbers: Dict[str, int]) -> None:
        with self._lock:
            self._items[call] = dict(numbers)

    def snapshot(self) -> Dict[Hashable, Dict[str, int]]:
        with self._lock:
            return {k: dict(v) for k, v in self._items.items()}

    def reset(self) -> None:
        with self._lock:
            self._items.clear()
