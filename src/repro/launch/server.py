"""Async streaming request plane over the continuous-batching engine.

A dependency-free asyncio HTTP/1.1 server (stdlib only — ``asyncio`` streams,
``hashlib``/``base64`` for the RFC 6455 WebSocket handshake) that exposes the
engine client API (engine.py: ``submit() -> rid``, ``subscribe``/``stream``,
``cancel``) over the wire:

====================  ========================================================
``POST /v1/generate``   body ``{"prompt": [ids], "max_new_tokens": N,
                        "deadline_s": x?, "stream": bool?, "detach": bool?}``.
                        Non-streaming: responds with the finished
                        ``Completion`` JSON (schema v1, engine.py).
                        ``"stream": true``: chunked NDJSON — one
                        ``{"event": "token"|"finish", ...}`` object per
                        line, exactly the subscribe() events.
                        ``"detach": true``: 202 + ``{"rid": N}`` right away;
                        attach a WebSocket for the tokens.
``GET /v1/stream``      WebSocket upgrade (``?rid=N``): every subscribe()
                        event as one text frame; closes after ``finish``.
                        A late upgrade replays the full stream (engine
                        subscribe semantics).
``POST /v1/cancel``     body ``{"rid": N}`` — cancels queued or mid-flight.
``GET /v1/stats``       engine occupancy, queue depth, prefix-cache stats,
                        resolved ServeConfig.
``GET /healthz``        liveness (200 once the engine thread runs).
``GET /metrics``        Prometheus text exposition of the engine metrics.
====================  ========================================================

Threading model: the engine is single-threaded by design (one JAX device
stream), so ALL engine mutation happens on one background *drive thread*
running the admit/step loop.  Handlers never touch the engine directly —
they post closures onto a thread-safe op inbox (``submit``, ``cancel``)
and get results back through ``concurrent.futures.Future``; token streams
ride the engine's thread-safe subscriber queues, bridged into coroutines
with ``asyncio.to_thread``.

Backpressure: when the admission queue (queued requests + unprocessed ops)
reaches ``ServeConfig.max_queue``, ``/v1/generate`` answers ``429
queue_full`` instead of enqueueing — the client retries, the engine never
builds an unbounded backlog.  A client that disconnects mid-stream gets its
request cancelled (slot evicted, blocks released) on the next drive tick.

    PYTHONPATH=src python -m repro.launch.server --arch yi-34b --reduced \
        --continuous --paged --port 8100

    curl -s localhost:8100/v1/generate -d \
        '{"prompt": [1,2,3], "max_new_tokens": 8}'
"""
from __future__ import annotations

import argparse
import asyncio
import base64
import dataclasses
import concurrent.futures
import hashlib
import json
import queue as queue_mod
import threading
import time
from typing import Optional

import numpy as np

from repro.launch.config import (ServeConfig, add_cli_args, config_from_args,
                                 use_compile_cache)
from repro.launch.engine import Request
from repro.obs.trace import annotate

_WS_GUID = "258EAFA5-E914-47DA-95CA-C5AB0DC85B11"


# ------------------------------------------------------------ engine bridge --

class EngineDriver:
    """Owns the drive thread: the only thread that mutates the engine."""

    def __init__(self, engine, max_queue: int):
        self.engine = engine
        self.max_queue = max_queue
        self._ops: queue_mod.Queue = queue_mod.Queue()
        self._stop = threading.Event()
        self._rid_lock = threading.Lock()
        self._next_rid = 0
        self.errors = 0              # engine exceptions the drive loop survived
        self._thread = threading.Thread(target=self._drive, daemon=True,
                                        name="engine-drive")
        self._t0 = time.perf_counter()

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=10)

    def clock(self) -> float:
        return time.perf_counter() - self._t0

    def _drive(self) -> None:
        eng = self.engine
        while not self._stop.is_set():
            progressed = False
            with annotate("repro.drive.ops"):
                while True:
                    try:
                        op = self._ops.get_nowait()
                    except queue_mod.Empty:
                        break
                    op(self.clock())
                    progressed = True
            try:
                if eng.queue and eng.free_slots():
                    eng.admit(clock=self.clock)
                    progressed = True
                if eng.active.any():
                    eng.step(now=self.clock())
                    progressed = True
            except Exception as e:  # noqa: BLE001 — the plane must survive
                # one poisoned request must not kill serving for everyone:
                # drop the queue head (admit raises before installing it),
                # terminate its stream, keep driving
                self.errors += 1
                print(json.dumps({"kind": "server/error", "error": str(e)}),
                      flush=True)
                if eng.queue:
                    bad = eng.queue.pop(0)
                    for q in eng._subs.get(bad.rid, ()):
                        q.put({"event": "finish", "rid": bad.rid,
                               "finish_reason": "error", "n_tokens": 0})
            if not progressed:
                time.sleep(0.001)

    # ----------------------------------------------------------- client ops --
    def queue_depth(self) -> int:
        return len(self.engine.queue) + self._ops.qsize()

    def submit(self, prompt, max_new_tokens: int,
               deadline_s: Optional[float]):
        """Thread-safe submit+subscribe; returns a Future of (rid, sub_q).

        Subscribing inside the same op as the submit makes the pair atomic
        on the drive thread — no token can be emitted between them, so the
        stream is complete from index 0 without replay races.
        """
        with self._rid_lock:
            rid = self._next_rid
            self._next_rid += 1
        fut: concurrent.futures.Future = concurrent.futures.Future()

        def op(now: float) -> None:
            req = Request(rid=rid,
                          prompt=np.asarray(prompt, np.int32),
                          max_new_tokens=max_new_tokens,
                          arrival_time=now, deadline_s=deadline_s)
            self.engine.submit(req)
            fut.set_result((rid, self.engine.subscribe(rid)))

        self._ops.put(op)
        return fut

    def cancel(self, rid: int) -> "concurrent.futures.Future":
        fut: concurrent.futures.Future = concurrent.futures.Future()
        self._ops.put(lambda now: fut.set_result(
            self.engine.cancel(rid, now=now)))
        return fut

    def unsubscribe(self, rid: int, q) -> None:
        self._ops.put(lambda now: self.engine.unsubscribe(rid, q))

    def stats(self) -> dict:
        eng = self.engine
        d = {"active_slots": int(eng.active.sum()),
             "max_slots": eng.max_slots,
             "queued": len(eng.queue),
             "completions": len(eng.completions),
             "queue_depth": self.queue_depth(),
             "max_queue": self.max_queue,
             "errors": self.errors}
        if hasattr(eng, "prefix_stats"):
            d["prefix_cache"] = eng.prefix_stats()
        return d


# ------------------------------------------------------------------- server --

class ServingServer:
    """Asyncio HTTP/1.1 + WebSocket front end over an :class:`EngineDriver`."""

    def __init__(self, engine, scfg: ServeConfig, metrics=None):
        self.scfg = scfg
        self.metrics = metrics
        self.driver = EngineDriver(engine, scfg.max_queue)
        self._server: Optional[asyncio.AbstractServer] = None

    async def start(self) -> None:
        self.driver.start()
        self._server = await asyncio.start_server(
            self._handle, self.scfg.host, self.scfg.port)
        # the bound port (port=0 picks a free one — the integration test uses
        # this) is authoritative, not the requested one
        self.port = self._server.sockets[0].getsockname()[1]
        print(json.dumps({"kind": "server/start", "host": self.scfg.host,
                          "port": self.port,
                          "config": self.scfg.to_json()}), flush=True)

    async def serve_forever(self) -> None:
        async with self._server:
            await self._server.serve_forever()

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
        self.driver.stop()

    # -------------------------------------------------------------- http ----
    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        try:
            request_line = await reader.readline()
            if not request_line:
                return
            try:
                method, target, _ = request_line.decode().split(" ", 2)
            except ValueError:
                await _respond(writer, 400, {"error": "bad request line"})
                return
            headers = {}
            while True:
                line = await reader.readline()
                if line in (b"\r\n", b"\n", b""):
                    break
                k, _, v = line.decode().partition(":")
                headers[k.strip().lower()] = v.strip()
            body = b""
            n = int(headers.get("content-length", 0))
            if n:
                body = await reader.readexactly(n)
            path, _, query = target.partition("?")
            params = dict(p.split("=", 1) for p in query.split("&") if "=" in p)
            await self._route(method, path, params, headers, body,
                              reader, writer)
        except (ConnectionResetError, asyncio.IncompleteReadError):
            pass
        finally:
            try:
                writer.close()
                await writer.wait_closed()
            except (ConnectionResetError, BrokenPipeError, OSError):
                pass

    async def _route(self, method, path, params, headers, body,
                     reader, writer) -> None:
        if path == "/healthz":
            await _respond(writer, 200, {"ok": True})
        elif path == "/metrics":
            if self.metrics is None:
                await _respond(writer, 404, {"error": "no metrics registry"})
            else:
                await _respond_text(writer, 200, self.metrics.prometheus(),
                                    ctype="text/plain; version=0.0.4")
        elif path == "/v1/stats":
            await _respond(writer, 200,
                           {**self.driver.stats(),
                            "config": self.scfg.to_json()})
        elif path == "/v1/cancel" and method == "POST":
            d = json.loads(body or b"{}")
            ok = await asyncio.wrap_future(self.driver.cancel(int(d["rid"])))
            await _respond(writer, 200, {"cancelled": ok})
        elif path == "/v1/generate" and method == "POST":
            await self._generate(body, reader, writer)
        elif path == "/v1/stream" and \
                headers.get("upgrade", "").lower() == "websocket":
            await self._websocket(params, headers, reader, writer)
        else:
            await _respond(writer, 404, {"error": f"no route {method} {path}"})

    async def _generate(self, body: bytes, reader: asyncio.StreamReader,
                        writer: asyncio.StreamWriter) -> None:
        try:
            d = json.loads(body)
            prompt = d["prompt"]
            if not (isinstance(prompt, list) and prompt and
                    all(isinstance(t, int) for t in prompt)):
                raise ValueError("prompt must be a non-empty list of int ids")
            max_new = int(d.get("max_new_tokens", self.scfg.gen))
            s_max = self.driver.engine.S_max
            if max_new < 1 or len(prompt) + max_new > s_max:
                raise ValueError(
                    f"prompt {len(prompt)} + max_new_tokens {max_new} "
                    f"exceeds this server's S_max {s_max}")
        except (ValueError, KeyError, TypeError) as e:
            await _respond(writer, 400, {"error": str(e)})
            return
        if self.driver.queue_depth() >= self.scfg.max_queue:
            # backpressure: bounded admission queue, client retries
            await _respond(writer, 429, {"error": "queue_full",
                                         "queue_depth":
                                         self.driver.queue_depth()})
            return
        rid, sub = await asyncio.wrap_future(self.driver.submit(
            prompt, max_new, d.get("deadline_s", self.scfg.deadline_s)))
        if d.get("detach"):
            # submit-only: hand back the rid; the client attaches a
            # WebSocket (GET /v1/stream?rid=N) for the token stream
            self.driver.unsubscribe(rid, sub)
            await _respond(writer, 202, {"rid": rid})
        elif d.get("stream"):
            await self._stream_ndjson(rid, sub, reader, writer)
        else:
            await self._await_completion(rid, sub, reader, writer)

    async def _next_event(self, sub, eof: "asyncio.Task"):
        """Next subscriber event, or None when the client hung up first.

        ``sub.get`` polls with a bounded timeout (an abandoned stream must
        not wedge a worker thread forever), and ``eof`` — a read() on the
        client socket — resolves the moment the peer closes, so disconnects
        are noticed even while the stream is idle between tokens.
        """
        while True:
            if eof.done():
                return None
            try:
                return await asyncio.to_thread(sub.get, True, 0.1)
            except queue_mod.Empty:
                continue

    async def _await_completion(self, rid, sub, reader, writer) -> None:
        eof = asyncio.ensure_future(reader.read())
        try:
            while True:
                ev = await self._next_event(sub, eof)
                if ev is None:       # disconnect while we were generating
                    await asyncio.wrap_future(self.driver.cancel(rid))
                    return
                if ev["event"] == "finish":
                    break
            comp = self.driver.engine.result(rid)
            await _respond(writer, 200, comp.to_json() if comp is not None
                           else {"rid": rid, "finish_reason": "cancel",
                                 "tokens": []})
        finally:
            eof.cancel()
            self.driver.unsubscribe(rid, sub)

    async def _stream_ndjson(self, rid, sub, reader, writer) -> None:
        writer.write(b"HTTP/1.1 200 OK\r\n"
                     b"Content-Type: application/x-ndjson\r\n"
                     b"Transfer-Encoding: chunked\r\n\r\n")
        eof = asyncio.ensure_future(reader.read())
        try:
            while True:
                ev = await self._next_event(sub, eof)
                if ev is None:
                    # client went away mid-stream: evict, free slot/blocks
                    await asyncio.wrap_future(self.driver.cancel(rid))
                    return
                chunk = (json.dumps(ev) + "\n").encode()
                writer.write(f"{len(chunk):x}\r\n".encode() + chunk + b"\r\n")
                await writer.drain()
                if ev["event"] == "finish":
                    writer.write(b"0\r\n\r\n")
                    await writer.drain()
                    return
        except (ConnectionResetError, BrokenPipeError, OSError):
            await asyncio.wrap_future(self.driver.cancel(rid))
        finally:
            eof.cancel()
            self.driver.unsubscribe(rid, sub)

    # --------------------------------------------------------- websocket ----
    async def _websocket(self, params, headers, reader, writer) -> None:
        key = headers.get("sec-websocket-key", "")
        accept = base64.b64encode(hashlib.sha1(
            (key + _WS_GUID).encode()).digest()).decode()
        writer.write((f"HTTP/1.1 101 Switching Protocols\r\n"
                      f"Upgrade: websocket\r\nConnection: Upgrade\r\n"
                      f"Sec-WebSocket-Accept: {accept}\r\n\r\n").encode())
        await writer.drain()
        rid = int(params.get("rid", -1))
        sub = self.driver.engine.subscribe(rid) if rid >= 0 else None
        if sub is None:
            await _ws_send(writer, json.dumps({"error": "missing rid"}))
            return
        closer = asyncio.ensure_future(_ws_read_until_close(reader, writer))
        try:
            while True:
                ev = await self._next_event(sub, closer)
                if ev is None:
                    # peer closed (or dropped) the socket mid-stream
                    await asyncio.wrap_future(self.driver.cancel(rid))
                    return
                await _ws_send(writer, json.dumps(ev))
                if ev["event"] == "finish":
                    writer.write(b"\x88\x00")  # close frame
                    await writer.drain()
                    return
        except (ConnectionResetError, BrokenPipeError, OSError):
            await asyncio.wrap_future(self.driver.cancel(rid))
        finally:
            closer.cancel()
            self.driver.unsubscribe(rid, sub)


async def _ws_send(writer: asyncio.StreamWriter, text: str) -> None:
    payload = text.encode()
    n = len(payload)
    if n < 126:
        head = bytes([0x81, n])
    elif n < 1 << 16:
        head = b"\x81\x7e" + n.to_bytes(2, "big")
    else:
        head = b"\x81\x7f" + n.to_bytes(8, "big")
    writer.write(head + payload)
    await writer.drain()


async def _ws_read_until_close(reader: asyncio.StreamReader,
                               writer: asyncio.StreamWriter) -> None:
    """Consume client frames (pong pings) until a close frame or EOF."""
    try:
        while True:
            head = await reader.readexactly(2)
            opcode = head[0] & 0x0F
            masked = head[1] & 0x80
            n = head[1] & 0x7F
            if n == 126:
                n = int.from_bytes(await reader.readexactly(2), "big")
            elif n == 127:
                n = int.from_bytes(await reader.readexactly(8), "big")
            mask = await reader.readexactly(4) if masked else b"\0\0\0\0"
            data = bytes(b ^ mask[i % 4]
                         for i, b in enumerate(await reader.readexactly(n)))
            if opcode == 0x8:        # close
                return
            if opcode == 0x9:        # ping -> pong
                writer.write(b"\x8a" + bytes([len(data)]) + data)
                await writer.drain()
    except (asyncio.IncompleteReadError, ConnectionResetError, OSError):
        return


async def _respond(writer, status: int, obj: dict) -> None:
    await _respond_text(writer, status, json.dumps(obj),
                        ctype="application/json")


async def _respond_text(writer, status: int, text: str,
                        ctype: str = "text/plain") -> None:
    phrase = {200: "OK", 202: "Accepted", 400: "Bad Request",
              404: "Not Found", 429: "Too Many Requests"}.get(status, "")
    payload = text.encode()
    writer.write((f"HTTP/1.1 {status} {phrase}\r\n"
                  f"Content-Type: {ctype}\r\n"
                  f"Content-Length: {len(payload)}\r\n"
                  f"Connection: close\r\n\r\n").encode() + payload)
    await writer.drain()


# --------------------------------------------------------------------- main --

def build_server(scfg: ServeConfig) -> ServingServer:
    """Model + engine + server from one validated ServeConfig."""
    from repro.obs.metrics import MetricsRegistry

    policy, _ = scfg.build_policy()
    model, params = scfg.load_model(policy)
    metrics = MetricsRegistry()
    engine = scfg.build_engine(model, params, policy, metrics=metrics)
    return ServingServer(engine, scfg, metrics=metrics)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=None, metavar="CFG.json",
                    help="ServeConfig JSON document; flags override")
    add_cli_args(ap)
    ns = ap.parse_args(argv)
    try:
        base = ServeConfig.load(ns.config) if ns.config else None
        scfg = config_from_args(ns, base=base)
        # the server *is* the request source — the continuous engine is the
        # only mode it can drive, so imply the flag instead of erroring
        scfg = dataclasses.replace(scfg, continuous=True).validate()
    except (ValueError, OSError) as e:
        ap.error(str(e))

    async def _run():
        server = build_server(scfg)
        await server.start()
        try:
            await server.serve_forever()
        except asyncio.CancelledError:
            pass
        finally:
            await server.stop()

    asyncio.run(_run())


if __name__ == "__main__":
    use_compile_cache()
    main()
