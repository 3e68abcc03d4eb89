"""repro.runtime — training loop, serving loop, fault tolerance."""

from . import fault, serve, spans, train_loop
from .fault import Preempted, PreemptionHandler, StragglerMonitor, retry
from .serve import Request, ServeConfig, Server
from .train_loop import TrainConfig, TrainState, build_train_step, init_state, run

__all__ = [
    "Preempted",
    "PreemptionHandler",
    "Request",
    "ServeConfig",
    "Server",
    "StragglerMonitor",
    "TrainConfig",
    "TrainState",
    "build_train_step",
    "fault",
    "init_state",
    "retry",
    "run",
    "serve",
    "spans",
    "train_loop",
]
