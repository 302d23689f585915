"""The client side of the gateway's wire protocol: stdlib and numpy only.

Copied from ``benchmarks/loadgen.py`` (``_b64``, ``_Client``), so the
benchmark's client does not change when that harness does.
"""
from __future__ import annotations

import base64
import http.client
import json

import numpy as np


def encode(a: np.ndarray) -> dict:
    """One array as the gateway decodes it: dtype, shape, base64 bytes."""
    return {"dtype": a.dtype.name, "shape": list(a.shape),
            "data": base64.b64encode(np.ascontiguousarray(a).tobytes())
            .decode("ascii")}


class Client:
    """One keep-alive HTTP/1.1 connection with JSON bodies."""

    def __init__(self, host: str, port: int, timeout_s: float):
        self.host, self.port, self.timeout_s = host, port, timeout_s
        self._conn = None

    def close(self) -> None:
        if self._conn is not None:
            try:
                self._conn.close()
            finally:
                self._conn = None

    def request(self, method: str, path: str, body: dict | None = None):
        """``(status, body)``: the body parsed as JSON where it is JSON."""
        data = json.dumps(body).encode() if body is not None else None
        reused = self._conn is not None
        if not reused:
            self._conn = http.client.HTTPConnection(
                self.host, self.port, timeout=self.timeout_s)
        try:
            self._conn.request(method, path, body=data,
                               headers={"Content-Type": "application/json"}
                               if data else {})
            r = self._conn.getresponse()
            raw = r.read()
        except (OSError, http.client.HTTPException) as e:
            self.close()
            # the gateway closes a keep-alive connection left idle past its
            # idle timeout; a request written to it is never read, so it
            # goes once more on a new connection (a window's seq makes a
            # second delivery safe: the gateway replays or refuses it)
            if reused and isinstance(e, (ConnectionResetError,
                                         BrokenPipeError)):
                return self.request(method, path, body)
            raise
        if r.getheader("Connection", "").lower() == "close":
            self.close()
        if raw[:1] in (b"{", b"["):
            try:
                return r.status, json.loads(raw)
            except ValueError:
                pass
        return r.status, raw
