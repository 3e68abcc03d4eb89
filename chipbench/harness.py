"""One run of one cell: set-up, the measured window, the check, the result.

Set-up builds the configuration's model with the program's own
``get_config`` and ``build_model``, makes the weights from the seed on
the device, makes one ``repro.runtime.Server`` and warms up exactly the
cell's programs.  The window then drives closed-loop waves through
``Server.step()`` for ``seconds``: before each wave the harness gives the
server fresh caches, the one way it answers a second wave correctly
today (its caches keep one length for all slots and never reset it).
After the window the program's state is freed and a sample of the
finished requests is checked against the plain reference.
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import statistics
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from chipbench import cells, peaks, trace, traffic, weights


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Served:
    plen: int
    out_len: int
    prompt: np.ndarray
    tokens: List[int]
    t_wave: float
    t_first: Optional[float] = None
    t_done: Optional[float] = None


@dataclasses.dataclass
class Window:
    seconds: float
    t_credit: float = 0.0  # when the last step's tokens were credited
    tokens: float = 0.0  # prompt and output tokens, the step cut by the end pro rata
    waves: int = 0
    requests: List[Served] = dataclasses.field(default_factory=list)
    compiles: int = 0
    server_compiles: Dict[str, int] = dataclasses.field(default_factory=dict)
    decode_kv: List[int] = dataclasses.field(default_factory=list)  # traced ticks
    prefill_plens: List[int] = dataclasses.field(default_factory=list)  # traced calls
    trace: Optional[trace.Trace] = None


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def devices(chips: int, require_tpu: bool = True):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's default platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} devices, the cell asks for {chips}")
    return devs


@dataclasses.dataclass
class Setup:
    devs: List[Any]
    family: Any  # the family's reference module
    model: Any
    pcfg: Any
    params: Any
    srv: Any


def build(cell: cells.Cell, seed: int, require_tpu: bool = True) -> Setup:
    """The program's model, the weights from the seed and one ``Server``
    at the cell's slots and cache length."""
    from repro.runtime import ServeConfig, Server

    devs = devices(cell.chips, require_tpu)
    tr, config = cell.traffic, cell.config
    family = cells.family_module(config["family"])
    model, pcfg = program_model(config)
    params = weights.make(model.abstract_params(), family.INIT, seed, pcfg.dtype,
                          config["vocab_size"])
    srv = Server(model, params, ServeConfig(batch_slots=tr["slots"], max_seq=tr["max_seq"]),
                 dtype=pcfg.dtype)
    return Setup(devs, family, model, pcfg, params, srv)


def program_model(config: Dict[str, Any]):
    """The program's model for ``config``: its own arch, with every key
    that the config maps set from the file."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import build_model

    prog = config["program"]
    over = {attr: config[key] for key, attr in prog["keys"].items()}
    cfg = dataclasses.replace(get_config(prog["arch"]), dtype=jnp.dtype(config["torch_dtype"]), **over)
    if cfg.padded_vocab != config["padded_vocab_size"]:
        raise ValueError(f"the program pads the vocabulary to {cfg.padded_vocab}, "
                         f"the config says {config['padded_vocab_size']}")
    return build_model(cfg), cfg


class CompileCounter:
    """Backend compiles, counted from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1


def run_wave(s: Setup, tr: Dict, plen: int, out_len: int, prompts: np.ndarray,
             win: Optional[Window], deadline: float, traced: bool = False) -> bool:
    """Serve one wave; False when the window closed inside it.

    Each step's tokens (the prompts it prefilled, the tokens it served)
    are credited when it returns; of the step that ends past the
    deadline, the share of its time that lies inside the window."""
    import jax
    from repro.runtime import Request

    srv = s.srv
    with jax.profiler.TraceAnnotation("reset"):
        srv.caches = None
        srv.caches = s.model.init_caches(tr["slots"], tr["max_seq"], dtype=s.pcfg.dtype)
    reqs = [Request(rid=i, prompt=prompts[i], max_tokens=out_len) for i in range(len(prompts))]
    for r in reqs:
        srv.submit(r)
    t_wave = time.perf_counter()
    served = [Served(plen, out_len, prompts[i], r.out_tokens, t_wave) for i, r in enumerate(reqs)]
    if win is not None:  # a wave cut by the window's end counts what it served inside it
        win.requests += served
    held = 0
    tick = 0
    while not all(r.done for r in reqs):
        with jax.profiler.TraceAnnotation("step.admit" if tick == 0 else "step.decode"):
            srv.step()
        t = time.perf_counter()
        if win is None:
            tick += 1
            continue
        if traced:  # this step's programs are in the trace, in or past the window
            if tick == 0:
                win.prefill_plens += [plen] * len(reqs)
            win.decode_kv.append(plen + tick + 1)
        now = sum(len(r.out_tokens) for r in reqs)
        gained = now - held + (plen * len(reqs) if tick == 0 else 0)
        held = now
        if t > deadline:
            win.tokens += gained * (deadline - win.t_credit) / (t - win.t_credit)
            return False
        win.tokens += gained
        win.t_credit = t
        for sv, r in zip(served, reqs):
            if sv.t_first is None and r.out_tokens:
                sv.t_first = t
            if sv.t_done is None and r.done:
                sv.t_done = t
        tick += 1
    if win is not None:
        win.waves += 1
    return True


def serve_window(s: Setup, tr: Dict, seed: int, vocab: int, seconds: float,
                 traced_waves: int, counter: CompileCounter) -> Window:
    import jax

    srv = s.srv
    win = Window(seconds)
    before = (counter.count, dict(srv.compiles))
    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced_waves else None
    start = time.perf_counter()
    win.t_credit = start
    deadline = start + seconds
    if log_dir:
        jax.profiler.start_trace(log_dir, profiler_options=trace.options())
    try:
        for i, (plen, out_len, prompts) in enumerate(traffic.waves(tr, seed, vocab)):
            traced = log_dir is not None and i < traced_waves
            if log_dir is not None and i == traced_waves:
                jax.profiler.stop_trace()
                win.trace = trace.read(log_dir)
                shutil.rmtree(log_dir, ignore_errors=True)
                log_dir = None
            if not run_wave(s, tr, plen, out_len, prompts, win, deadline, traced):
                break
    finally:
        if log_dir is not None:
            jax.profiler.stop_trace()
            win.trace = trace.read(log_dir)
            shutil.rmtree(log_dir, ignore_errors=True)
    win.compiles = counter.count - before[0]
    win.server_compiles = {k: srv.compiles[k] - before[1].get(k, 0) for k in srv.compiles}
    return win


def pick(requests: List[Served], k: int, seed: int) -> List[Served]:
    """``k`` finished requests drawn from the seed, the longest among them."""
    done = [r for r in requests if r.t_done is not None]
    rng = np.random.default_rng([seed, 2])
    done = [done[i] for i in rng.permutation(len(done))]
    if not done:
        return []
    longest = max(done, key=lambda r: r.plen + r.out_len)
    return [longest] + [r for r in done if r is not longest][: k - 1]


def _sequence(r: Served, max_seq: int, rows_len: int):
    n = len(r.tokens)
    seq = np.zeros(max_seq, np.int32)
    seq[: r.plen] = r.prompt
    seq[r.plen: r.plen + n - 1] = r.tokens[:-1]
    rows = np.full(rows_len, r.plen + n - 2, np.int32)
    rows[:n] = r.plen - 1 + np.arange(n)
    return seq, rows


def logit_gaps(family, config: Dict, params, picks: List[Served], tr: Dict,
               control: Optional[str] = None) -> np.ndarray:
    """For every served token of ``picks``: by how much its reference logit
    lies below the reference's best at that position.  With ``control``,
    the token is the one the reference in that format puts first."""
    import jax.numpy as jnp

    ref_fn = family.logits_fn(config)
    ctl_fn = family.logits_fn(config, control) if control else None
    rows_len = max(tr["output_buckets"])
    gaps = []
    for r in picks:
        seq, rows = _sequence(r, tr["max_seq"], rows_len)
        n = len(r.tokens)
        ref = np.asarray(ref_fn(params, jnp.asarray(seq), jnp.asarray(rows)))[:n]
        chosen = (np.asarray(r.tokens) if ctl_fn is None else
                  np.asarray(ctl_fn(params, jnp.asarray(seq), jnp.asarray(rows)))[:n].argmax(-1))
        gaps.append(ref.max(-1) - ref[np.arange(n), chosen])
    return np.concatenate(gaps) if gaps else np.zeros(0)


def check(s: Setup, config: Dict, picks: List[Served], tr: Dict, limit: float,
          control: Optional[str] = None) -> Tuple[float, int, bool]:
    """``logit_gap_max`` of ``picks`` (or of the control put in the
    program's place), the tokens it covers, and whether it is within
    ``limit``: the one comparison that decides ``correct``."""
    gaps = logit_gaps(s.family, config, s.params, picks, tr, control)
    widest = float(gaps.max()) if gaps.size else float("inf")
    return widest, int(gaps.size), widest <= limit


def free_server(s: Setup) -> None:
    """Drop the program's state, so that the reference has the chip."""
    s.srv.caches = None
    s.srv.last_logits = None
    s.srv = None
    gc.collect()


def p90(values: List[float]) -> float:
    if len(values) == 1:
        return values[0]
    return statistics.quantiles(values, n=10, method="inclusive")[8]


def end_to_end(win: Window, setup_s: float) -> Dict[str, Dict[str, Any]]:
    ttft = [1e3 * (r.t_first - r.t_wave) for r in win.requests if r.t_first is not None]
    tpot = [1e3 * (r.t_done - r.t_first) / (r.out_len - 1)
            for r in win.requests if r.t_done is not None and r.out_len > 1]
    done = sum(r.t_done is not None for r in win.requests)
    log(f"[window] {win.seconds:g}s: {win.waves} waves, {len(win.requests)} requests, {done} done, "
        f"{win.tokens:.1f} tokens; ttft n={len(ttft)} median {statistics.median(ttft) if ttft else float('nan'):.4f} ms; "
        f"tpot n={len(tpot)} median {statistics.median(tpot) if tpot else float('nan'):.4f} ms")
    out = {
        "tokens_per_s": {"value": win.tokens / win.seconds, "unit": "tokens/s"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }
    if ttft:
        out["ttft_p90_ms"] = {"value": p90(ttft), "unit": "ms"}
    if tpot:
        out["tpot_p90_ms"] = {"value": p90(tpot), "unit": "ms"}
    return out


def per_layer(cell: cells.Cell, win: Window, slots: int, kind: str) -> Dict[str, Dict[str, Any]]:
    ctx = {"trace": win.trace, "decode_kv": win.decode_kv, "prefill_plens": win.prefill_plens,
           "slots": slots, "config": cell.config, "peaks": peaks.peaks_for(kind)}
    out = {}
    for m in cell.per_layer:
        value = cells.metric_reader(m["name"]).read(ctx)
        if value is None:
            log(f"[trace] {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(cell: cells.Cell, seed: int, seconds: float, trace_on: bool, t0: float,
        require_tpu: bool = True) -> Dict[str, Any]:
    """One run; returns the result object that ``run.py`` prints."""
    counter = CompileCounter()
    tr, config = cell.traffic, cell.config
    s = build(cell, seed, require_tpu)
    dev = s.devs[0]
    vocab = config["vocab_size"]
    for plen, out_len, prompts in traffic.warmup_waves(tr, seed, vocab):
        run_wave(s, tr, plen, out_len, prompts, None, float("inf"))
    # as servers do after warm-up: drop set-up's garbage, and keep what it
    # leaves (traces, executables, weights) out of later collections, so
    # that no long collection of set-up's objects falls in the window
    gc.collect()
    gc.freeze()
    setup_s = time.time() - t0
    log(f"[setup] {setup_s:.3f}s; compiles so far {counter.count}, server {s.srv.compiles}")

    traced_waves = len(tr["prompt_buckets"]) if trace_on else 0
    win = serve_window(s, tr, seed, vocab, seconds, traced_waves, counter)
    log(f"[window] compiles inside the window: {win.compiles} (server {win.server_compiles})")
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"[memory] peak_bytes_in_use={peak} bytes_limit={stats.get('bytes_limit')}")
    free_server(s)

    picks = pick(win.requests, tr["check_requests"], seed)
    t = time.perf_counter()
    limit = cell.limits["logit_gap_max"]
    widest, n, within = check(s, config, picks, tr, limit)
    log(f"[check] {len(picks)} requests, {n} served tokens against the reference "
        f"in {time.perf_counter() - t:.3f}s")
    failed = sum(1 for r in win.requests if r.t_done is not None and len(r.tokens) != r.out_len)
    correct = bool(within and failed == 0)

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(s.devs),
              "memory_peak_bytes": peak}
    result: Dict[str, Any] = {"correct": correct, "attempted": len(win.requests), "failed": failed}
    if trace_on:
        metrics = per_layer(cell, win, tr["slots"], dev.device_kind)
        lo, hi = win.trace.window
        device.update(busy_s=trace.busy_seconds(win.trace), window_s=hi - lo)
        result["breakdown"] = trace.breakdown(win.trace)
    else:
        measured = end_to_end(win, setup_s)
        metrics = {m["name"]: measured[m["name"]] for m in cell.end_to_end if m["name"] in measured}
    result.update(metrics=metrics, device=device)
    result["checks"] = {"logit_gap_max": {"value": widest, "limit": limit}}
    log(f"check logit_gap_max {widest!r} limit {limit!r}")
    return result
