"""repro.runtime — the serving loop and its host spans.

Training code imports ``repro.runtime.train_loop`` by name, and the
fault primitives live in ``repro.fault``, so importing ``Server`` loads
neither.
"""

from . import serve, spans
from .serve import Request, ServeConfig, Server

__all__ = ["Request", "ServeConfig", "Server", "serve", "spans"]
