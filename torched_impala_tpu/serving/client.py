"""InProcessClient: the same-process serving client.

The thinnest possible transport over `PolicyServer.connect/submit` —
function calls and a write-once result cell, no serialization. This is
what the evaluator uses (`run_episodes(..., client=...)`) and what
in-process actor fleets would use; cross-process clients ride the shm
request ring (serving/shm_ring.py) instead.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from torched_impala_tpu.serving.server import (
    PolicyServer,
    ServeResult,
    _ResultCell,
)


class InProcessClient:
    """One serving connection: sticky routing, server-held recurrent state.

    `act()` is the synchronous surface (submit + wait); `act_async()`
    returns the result cell for callers that pipeline their own waits
    (a concurrent-client driver). Use as a context manager or
    call `close()` so the slot frees for the next client.
    """

    def __init__(
        self,
        server: PolicyServer,
        greedy: bool = True,
        timeout_s: float = 30.0,
        client_id: Optional[int] = None,
    ) -> None:
        self._server = server
        self._timeout_s = timeout_s
        self._slot = server.connect(greedy=greedy, client_id=client_id)
        self._closed = False

    @property
    def slot(self) -> int:
        return self._slot

    @property
    def server(self) -> PolicyServer:
        """The replica behind this connection (the fleet router reads
        it to invalidate cached slots when a replica dies)."""
        return self._server

    def act_async(
        self,
        obs: np.ndarray,
        first: bool,
        deadline_s: Optional[float] = None,
    ) -> _ResultCell:
        return self._server.submit(
            self._slot, obs, first, deadline_s=deadline_s
        )

    def act_full(self, obs: np.ndarray, first: bool) -> ServeResult:
        """Blocking request returning the full (action, version, label,
        wave) provenance."""
        return self.act_async(obs, first).result(self._timeout_s)

    def act(self, obs: np.ndarray, first: bool) -> int:
        """Blocking request returning just the action int — the
        evaluator-facing surface."""
        return self.act_full(obs, first).action

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self._server.disconnect(self._slot)

    def __enter__(self) -> "InProcessClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
