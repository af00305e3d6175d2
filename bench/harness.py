"""One run of one cell: the production request plane under a traffic mix.

The server is ``launch.server.ServingServer`` over the paged continuous
engine, built in this process by the three steps of ``build_server``
(``ServeConfig.build_policy``, the weights in served form, ``build_engine``
with a ``MetricsRegistry``), with the model from the configuration file.
Clients are threads of this process; each request is a streamed
``POST /v1/generate`` over localhost and every token line is stamped on
the client's clock when it is read.

The harness reads the program only through its public surface: the HTTP
routes, the engine's ``MetricsRegistry`` (its ``slots_active`` histogram)
and its request records (``engine.completions``: prompt length, admission
time, the time of every token), from which each decode step's live rows and
each prefill's prompt are rebuilt (``context.records``).
"""
from __future__ import annotations

import asyncio
import dataclasses
import http.client
import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import reference
import traffic
import weights

now = time.perf_counter


# ------------------------------------------------------------------ model ---

def model_cfg(m: dict, cfg: dict):
    """The program's ``ModelCfg`` for a configuration file's sizes: the
    fields every configuration has, and its architecture module's
    (``reference/<name>.py``)."""
    from repro.configs.base import ModelCfg
    return ModelCfg(
        name=cfg["name"], family=cfg["family"],
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        vocab=m["vocab_size"], norm_eps=float(m["rms_norm_eps"]),
        tie_embeddings=bool(m.get("tie_word_embeddings", False)),
        capacity_factor=float(cfg["program"]["capacity_factor"]),
        **reference.load(m).model_fields(m))


# Fields the configuration file sets itself: its name, the published RMSNorm
# epsilon (the registered architectures keep the program's default), and the
# expert capacity it chooses (``program.capacity_factor``).
OWN_FIELDS = ("name", "norm_eps", "capacity_factor")


def check_registered(cfg: dict, mc) -> None:
    """At the published size the sizes must be the program's registered
    architecture's: a configuration never runs a model the program does
    not list.  Every ``ModelCfg`` field is compared but ``OWN_FIELDS``."""
    from repro.configs import get_arch
    arch = dataclasses.asdict(get_arch(cfg["program"]["arch"]))
    for f, v in dataclasses.asdict(mc).items():
        if f not in OWN_FIELDS and arch[f] != v:
            raise ValueError(f"{cfg['name']}: {f} {v} differs from "
                             f"{arch['name']}'s {arch[f]}")


@dataclasses.dataclass
class Cell:
    name: str
    cfg: dict            # configuration file
    m: dict              # published sizes (test sizes in a rehearsal)
    mix: dict            # traffic mix
    seed: int


def serve_config(cell: Cell):
    from repro.launch.config import ServeConfig
    s, mix = cell.cfg["serve"], cell.mix
    gen = mix["output"]["max"]
    page_bytes = cell.m.get("page_bytes", s["page_bytes"])
    block_tokens = page_bytes // reference.load(cell.m).page_row_bytes(cell.m)
    return ServeConfig(
        arch=cell.cfg["program"]["arch"], continuous=True, paged=True,
        policy=s["policy"], quantize_weights=s["quantize_weights"],
        page_bytes=page_bytes,
        n_blocks=mix["kv_pool_tokens"] // block_tokens,
        max_slots=mix["max_slots"], prompt_len=mix["s_max"] - gen, gen=gen,
        temperature=s["temperature"], seed=cell.seed % (1 << 31), port=0,
        max_queue=mix["max_queue"]).validate()


# ---------------------------------------------------------------- records ---

@dataclasses.dataclass
class Req:
    i: int
    prompt: list
    max_new: int
    t_sched: float = 0.0
    t_send: float = 0.0
    rid: int = -1
    stamps: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    finish: str = ""
    error: str = ""
    t_done: float = 0.0
    cut: bool = False     # cancelled by the harness when the window closed

    @property
    def ok(self) -> bool:
        return (not self.error and self.finish == "max_new"
                and len(self.tokens) == self.max_new)

    @property
    def cut_short(self) -> bool:
        """Cancelled in flight at the window's close: neither served whole
        nor failed."""
        return self.cut and not self.error and self.finish == "cancel"


# ----------------------------------------------------------------- server ---

class Server:
    """ServingServer on its own event-loop thread in this process."""

    def __init__(self, cell: Cell):
        from repro.launch.server import ServingServer
        from repro.models.layers import quantize_params
        from repro.models.registry import build_model
        from repro.obs.metrics import MetricsRegistry

        scfg = serve_config(cell)
        policy, _ = scfg.build_policy()
        model = build_model(model_cfg(cell.m, cell.cfg))
        params = weights.served_params(cell.m, cell.seed, policy,
                                       quantize_params)
        metrics = MetricsRegistry()
        engine = scfg.build_engine(model, params, policy, metrics=metrics)
        self.server = ServingServer(engine, scfg, metrics=metrics)
        self.engine = engine
        self.slots_active = metrics.histogram("slots_active")
        # the engine's records are on the drive thread's clock
        self.offset = now() - self.server.driver.clock()
        self.loop = asyncio.new_event_loop()
        started, box = threading.Event(), {}

        def serve():
            asyncio.set_event_loop(self.loop)
            try:
                self.loop.run_until_complete(self.server.start())
            except Exception as e:  # noqa: BLE001 -- re-raised below
                box["error"] = e
                return
            finally:
                started.set()
            self.loop.run_forever()

        self.thread = threading.Thread(target=serve, daemon=True,
                                       name="bench-server")
        self.thread.start()
        started.wait()
        if "error" in box:
            raise box["error"]
        self.port = self.server.port

    def stop(self) -> None:
        asyncio.run_coroutine_threadsafe(self.server.stop(),
                                         self.loop).result(60)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(60)
        self.loop.close()


def cancel(port: int, rid: int) -> None:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("POST", "/v1/cancel", json.dumps({"rid": rid}),
                     {"Content-Type": "application/json"})
        conn.getresponse().read()
    finally:
        conn.close()


def stream(port: int, req: Req, timeout: float, cut=None) -> Req:
    """One streamed request; stamps each token line as it is read.  Once
    the event ``cut`` is set, the request is cancelled at its next token and
    read to its end."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        req.t_send = now()
        conn.request("POST", "/v1/generate", json.dumps(
            {"prompt": req.prompt, "max_new_tokens": req.max_new,
             "stream": True}), {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            req.error = f"HTTP {resp.status}: {resp.read()[:200]!r}"
            return req
        while True:
            line = resp.readline()
            if not line:
                req.error = req.error or "stream ended without finish"
                return req
            t = now()
            ev = json.loads(line)
            if ev["event"] == "token":
                req.rid = ev["rid"]
                req.stamps.append(t)
                req.tokens.append(ev["token"])
                if cut is not None and cut.is_set() and not req.cut:
                    req.cut = True
                    cancel(port, req.rid)
            elif ev["event"] == "finish":
                req.rid = ev["rid"]
                req.finish = ev["finish_reason"]
                req.t_done = t
                return req
    except Exception as e:  # noqa: BLE001 -- a failed request is a record
        req.error = f"{type(e).__name__}: {e}"
        return req
    finally:
        conn.close()


# ----------------------------------------------------------------- driver ---

@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    attempted: list       # requests scheduled in the window
    all: list             # every request sent, warm-up ramp included
    lateness: list        # open loop: send time minus scheduled time
    trace: tuple = None   # (t0, t1) of the traced part, on this clock
    compiles: int = 0     # compiles that happened inside the window
    occupancy: tuple = None  # (decode steps, live slots summed) in the window

    def finished(self) -> list:
        """Requests served whole that finished in the window, whenever sent,
        or that were sent in it (an open loop drains them)."""
        return [r for r in self.all if r.ok and (
            self.t0 <= r.t_done < self.t1 or self.t0 <= r.t_sched < self.t1)]


class CompileCounter:
    """Counts the programs compiled while ``on`` (inside the window)."""

    def __init__(self):
        import jax
        self.n, self.on = 0, False

        def listen(event, duration, **kw):
            if self.on and event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


def warm_up(srv: Server, reqs: traffic.Requests, timeout: float) -> None:
    """Compile every shape the window uses: one request per prompt bucket
    (its prefill, the decode step, the first-token sampling)."""
    rng = np.random.default_rng(0)
    for n, T in enumerate(reqs.warmup_lengths()):
        ids = rng.integers(0, reqs.vocab, T).tolist()
        r = stream(srv.port, Req(-1 - n, ids, 2), timeout)
        if not r.ok:
            raise RuntimeError(f"warm-up request of {T} tokens failed: "
                               f"{r.error or r.finish}")


def drive(srv: Server, reqs: traffic.Requests, seconds: float, *,
          trace_dir: str | None, compiles: CompileCounter) -> Window:
    mix = reqs.mix
    timeout = seconds + mix["drain_s"] + 60
    if mix["loop"] == "closed":
        return _closed(srv, reqs, seconds, timeout, trace_dir, compiles)
    return _open(srv, reqs, seconds, timeout, trace_dir, compiles)


def _occupancy(srv: Server) -> tuple:
    h = srv.slots_active
    return h.n, h.sum


def _traced_window(srv: Server, seconds: float, trace_dir, compiles):
    """Open the window and hold it open for ``seconds``; with ``trace_dir``,
    trace a part of it.  Returns (t0, t1, traced (t0, t1) or None,
    occupancy)."""
    import jax
    occ0 = _occupancy(srv)
    t0 = now()
    compiles.on = True
    traced = None
    if trace_dir:
        lead = min(2.0, 0.2 * seconds)
        span = min(6.0, seconds - 2 * lead)
        time.sleep(max(0.0, t0 + lead - now()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            a = now()
            time.sleep(span)
            b = now()
        jax.profiler.stop_trace()
        traced = (a, b)
    time.sleep(max(0.0, t0 + seconds - now()))
    t1 = now()
    occ1 = _occupancy(srv)
    compiles.on = False
    return t0, t1, traced, (occ1[0] - occ0[0], occ1[1] - occ0[1])


def _closed(srv, reqs, seconds, timeout, trace_dir, compiles) -> Window:
    mix, eng = reqs.mix, srv.engine
    lock, stop = threading.Lock(), threading.Event()
    sent: list = []
    counter = iter(range(1 << 30))

    def client(k):
        while not stop.is_set():
            with lock:
                i = next(counter)
            prompt, max_new = reqs.spec(i)
            r = Req(i, prompt, max_new)
            r.t_sched = now()
            with lock:
                sent.append(r)
            stream(srv.port, r, timeout, cut=stop)

    threads = [threading.Thread(target=client, args=(k,), daemon=True,
                                name=f"bench-client-{k}")
               for k in range(mix["clients"])]
    for t in threads:
        t.start()
    # the window opens once every slot has been filled for the first time
    deadline = now() + timeout
    while not eng.active.all():
        if now() > deadline or not any(t.is_alive() for t in threads):
            raise RuntimeError("the slots never all filled")
        time.sleep(0.005)
    t0, t1, traced, occ = _traced_window(srv, seconds, trace_dir, compiles)
    # what is in flight at the close is cancelled, not drained: the window's
    # numbers end with it, and the check reads the requests finished in it
    stop.set()
    deadline = now() + mix["drain_s"]
    for t in threads:
        t.join(max(0.0, deadline - now()))
    with lock:
        done = list(sent)
    attempted = [r for r in done if t0 <= r.t_sched < t1]
    return Window(t0, t1, attempted, done, [], traced, compiles.n, occ)


def _open(srv, reqs, seconds, timeout, trace_dir, compiles) -> Window:
    mix = reqs.mix
    plan = reqs.arrivals(seconds)
    pool = ThreadPoolExecutor(max_workers=mix["max_queue"] + mix["max_slots"],
                              thread_name_prefix="bench-client")
    sent = []
    start = now() + 0.05
    t0 = start + mix["ramp_s"]

    def schedule():
        for i, a in enumerate(plan):
            t = start + a
            time.sleep(max(0.0, t - now()))
            prompt, max_new = reqs.spec(i)
            r = Req(i, prompt, max_new, t_sched=t)
            sent.append((r, pool.submit(stream, srv.port, r, timeout)))

    sched = threading.Thread(target=schedule, daemon=True,
                             name="bench-schedule")
    sched.start()
    time.sleep(max(0.0, t0 - now()))
    t0, t1, traced, occ = _traced_window(srv, seconds, trace_dir, compiles)
    sched.join()
    deadline = now() + mix["drain_s"]
    for r, f in sent:
        try:
            f.result(max(0.0, deadline - now()))
        except Exception:  # noqa: BLE001 -- unfinished counts as failed
            r.error = r.error or "not finished within the drain"
    pool.shutdown(wait=False, cancel_futures=True)
    reqs_all = [r for r, _ in sent]
    lateness = [r.t_send - r.t_sched for r in reqs_all if r.t_send]
    attempted = [r for r in reqs_all if t0 <= r.t_sched < t1]
    return Window(t0, t1, attempted, reqs_all, lateness, traced, compiles.n,
                  occ)


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
