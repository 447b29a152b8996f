"""Serving HTTP server: a stdlib generate endpoint over the engine.

The thin network front of the serving subsystem — deliberately the
same stdlib-only discipline as ``telemetry/metrics_server.py`` (no
framework dependency for a repo whose serving claims must run in the
CI container):

- ``POST /generate`` — JSON ``{"prompt_ids": [...]}`` or (byte-vocab
  models) ``{"text": "..."}``, plus ``max_new_tokens``; blocks until
  the request drains through the continuous-batching engine and
  returns ``{"tokens", "text"?, "ttft_s", "latency_s"}``. Requests
  from many connections interleave in the engine's running batch —
  the HTTP handler threads only enqueue and wait.
- ``POST /generate`` with ``"stream": true`` — chunked
  transfer-encoding (HTTP/1.1): one JSON line per token, flushed the
  moment the engine samples it (``{"token": N}``), then a final
  ``{"done": true, "tokens", "ttft_s", "latency_s", ...}`` line.
  Tokens ride the engine's per-token listeners
  (``Engine.add_token_listener``) through a per-request queue — the
  engine thread never blocks on a slow streaming client.
- ``GET /healthz`` — 200 with queue/slot stats while the engine
  thread is alive.
- live gauges — the engine's telemetry records flow through the
  ambient sink to a ``MetricsServer`` (``metrics_port``), which
  exports the ``dtt_serving_*`` gauges next to the training set: one
  observer pattern, one ``/metrics`` schema, two workloads.

Threading model: HTTP handlers never touch the engine. They append
to a mailbox; the single engine thread admits mailbox requests,
steps the engine, and signals completion events. The engine stays
single-threaded (its allocator and jit carry no locks), and a
slow/disconnected client cannot stall decode.

CLI::

    python -m distributed_training_tpu.serving.server \
        --artifact model.msgpack --plan serving_8dev_cpu_decode \
        --port 8100 --metrics-port 8101
"""

from __future__ import annotations

import argparse
import http.server
import json
import logging
import queue
import threading
import time

import numpy as np

from distributed_training_tpu.telemetry import phase

logger = logging.getLogger(__name__)


def debug_requests_snapshot(engine) -> dict:
    """In-flight request table — the ``/debug/requests`` body.

    Engine bookkeeping only — slot table + page tables, zero device
    touch. Best-effort snapshot: the engine thread mutates slots
    between reads, so a sequence finishing mid-render is simply
    absent. Module-level so the incident recorder can capture the
    same snapshot into a bundle without going through HTTP."""
    reqs = []
    for s in list(engine.slots):
        if s is None:
            continue
        try:
            reqs.append({
                "id": s.req.id,
                "tenant": s.req.tenant,
                "group": engine.group_of_slot(s.slot),
                "slot": s.slot,
                "prompt_tokens": s.prompt_len,
                "prefilled": s.prefilled,
                "generated": len(s.generated),
                "pages_held":
                    engine.cache.pages_of(s.req.id),
                "session": s.req.session,
                "spans": list(s.trace),
                "weights_versions": [list(p) for p in s.versions]})
        except KeyError:
            continue  # freed between reads
    return {
        "in_flight": len(reqs),
        "queue_depth": len(engine.queue),
        "draining": bool(getattr(engine, "draining", False)),
        "weights": {
            "version": engine.weights_version,
            "provenance": engine.weights_provenance,
            "swaps": dict(engine.swap_stats)},
        "requests": reqs}


class ServingServer:
    """HTTP front + engine thread over a built Engine."""

    def __init__(self, engine, port: int = 0,
                 metrics_port: int | None = None, telemetry=None,
                 max_queue_depth: int = 0,
                 retry_after_s: float = 1.0,
                 incident_dir: str | None = None):
        self.engine = engine
        self._requested_port = port
        self.port: int | None = None
        self._mailbox: list = []
        self._done: dict[str, dict] = {}
        self._events: dict[str, threading.Event] = {}
        self._streams: dict[str, queue.Queue] = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._httpd = None
        self._engine_thread = None
        self._http_thread = None
        self._next_id = 0
        self._telemetry = telemetry
        # Admission control + resilience knobs: with
        # ``max_queue_depth`` > 0, POST /generate sheds load (503 +
        # Retry-After) once queue+mailbox reach it — a bounded queue
        # beats clients silently timing out behind an unbounded one.
        # ``incident_dir`` set → an engine-thread exception leaves a
        # flight-recorder bundle there (kind ``engine_crash``).
        self.max_queue_depth = int(max_queue_depth)
        self.retry_after_s = float(retry_after_s)
        self.incident_dir = incident_dir
        # The engine thread's cause of death, when it died to an
        # exception (healthz reports "unhealthy"; new work is shed).
        self.engine_error: str | None = None
        self.leaked_threads = 0
        # Control commands (drain / weight swap) execute BETWEEN
        # steps ON the engine thread — the engine stays
        # single-threaded; public drain()/swap_weights() enqueue here
        # and wait.
        self._control: list = []
        # A MetricsServer ALWAYS backs GET /metrics on the serving
        # port (its renderer + observer, no second socket) so a
        # serving-only deployment needs no coordinator metrics port;
        # with ``metrics_port`` set the same instance additionally
        # binds the standalone endpoint the trainer convention uses.
        from distributed_training_tpu.telemetry import MetricsServer
        self._metrics_owns_port = metrics_port is not None
        self.metrics = MetricsServer(
            metrics_port if metrics_port is not None else 0,
            telemetry=telemetry)

    def debug_snapshot(self) -> dict:
        """The ``/debug/requests`` body, callable in-process — the
        incident recorder's ``serving_snapshot`` hook."""
        return debug_requests_snapshot(self.engine)

    @property
    def draining(self) -> bool:
        return bool(getattr(self.engine, "draining", False))

    def _control_call(self, cmd: str, args,
                      timeout: float = 300.0):
        """Run a drain/swap command ON the engine thread (started
        server) or inline (engine thread not running — the in-process
        test path); either way the engine is only ever touched from
        one thread at a time."""
        t = self._engine_thread
        if t is None or not t.is_alive():
            done = threading.Event()
            slot: dict = {}
            self._control.append((cmd, args, done, slot))
            self._run_control(self.engine)
        else:
            done = threading.Event()
            slot = {}
            with self._lock:
                self._control.append((cmd, args, done, slot))
            if not done.wait(timeout):
                raise TimeoutError(f"{cmd} command timed out after "
                                   f"{timeout}s")
        if "error" in slot:
            raise slot["error"]
        return slot.get("result")

    def swap_weights(self, params, version: str,
                     provenance: dict | None = None,
                     timeout: float = 300.0):
        """Live weight hot-swap through the engine thread
        (``Engine.swap_weights`` — all gates, zero recompiles).
        Raises the engine's refusal verbatim; the incumbent weights
        keep serving on any failure."""
        return self._control_call("swap", (params, version,
                                           provenance), timeout)

    def drain(self, deadline_s: float | None = None,
              timeout: float = 300.0) -> dict:
        """Graceful drain through the engine thread: admission stops
        (POST /generate starts 503ing with Retry-After, /healthz
        reports "draining"), in-flight work finishes (or persists at
        the deadline), and the per-request outcome report returns.
        ``resume_admission()`` reopens the front door."""
        return self._control_call("drain", deadline_s,
                                  max(timeout, (deadline_s or 0) * 2))

    def resume_admission(self, timeout: float = 60.0) -> None:
        self._control_call("undrain", None, timeout)

    # -- engine thread -----------------------------------------------------

    def _engine_loop(self) -> None:
        from distributed_training_tpu.serving.engine import Request

        eng = self.engine
        try:
            self._engine_loop_inner(eng, Request)
        except Exception as e:  # noqa: BLE001 — the engine thread's
            # last act: record WHY it died (bundle + event + error
            # replies) instead of dying silently with every in-flight
            # client blocked until timeout.
            self._on_engine_crash(e)

    def _on_engine_crash(self, exc: Exception) -> None:
        """Engine-thread postmortem: mark unhealthy, fail every
        waiting client, emit ``serving_engine_crash``, and (with
        ``incident_dir``) leave a flight-recorder bundle carrying the
        ``/debug/requests`` snapshot and the last weight-swap
        provenance — the evidence ``--doctor`` classifies as
        ``serving_engine_crash``."""
        from distributed_training_tpu import telemetry as tel

        err = f"{type(exc).__name__}: {exc}"
        self.engine_error = err
        logger.exception("serving engine thread died: %s", err)
        eng = self.engine
        snap = None
        try:
            snap = debug_requests_snapshot(eng)
        except Exception:  # noqa: BLE001 — evidence is best-effort;
            # the postmortem must survive a half-broken engine.
            logger.warning("debug snapshot failed during crash "
                           "postmortem", exc_info=True)
        # Event BEFORE the bundle so its events_tail carries the
        # record the doctor keys on.
        tel.event("serving_engine_crash", error=err,
                  launches=getattr(eng, "launch_count", None),
                  weights_version=getattr(eng, "weights_version",
                                          None),
                  in_flight=eng.in_flight,
                  queue_depth=len(eng.queue))
        if self.incident_dir:
            from distributed_training_tpu.telemetry.incident import (
                write_incident_bundle)
            write_incident_bundle(
                self.incident_dir, reason=err, kind="engine_crash",
                events_tail=tel.current().tail(),
                extra={"launch_count": getattr(eng, "launch_count",
                                               None),
                       "weights_version": getattr(
                           eng, "weights_version", None),
                       "weights_provenance": getattr(
                           eng, "weights_provenance", None),
                       "swap_stats": dict(getattr(eng, "swap_stats",
                                                  {}))},
                serving=snap)
        with self._lock:
            events, self._events = self._events, {}
            streams, self._streams = self._streams, {}
            for rid, ev in events.items():
                self._done[rid] = {"id": rid,
                                   "error": f"engine crashed: {err}"}
                ev.set()
        for rid, sq in streams.items():
            sq.put(("done", {"id": rid,
                             "error": f"engine crashed: {err}"}))

    def _run_control(self, eng) -> None:
        """Execute queued drain/swap commands on the engine thread.
        Results (or the refusal exception) hand back through each
        command's slot; the caller re-raises in its own thread."""
        with self._lock:
            cmds, self._control = self._control, []
        for cmd, args, done, slot in cmds:
            try:
                if cmd == "swap":
                    params, version, provenance = args
                    slot["result"] = eng.swap_weights(
                        params, version, provenance)
                elif cmd == "drain":
                    slot["result"] = eng.drain(args)
                elif cmd == "undrain":
                    eng.draining = False
                    slot["result"] = True
            except Exception as e:  # noqa: BLE001 — a REFUSED swap
                # must reach its caller, never kill the engine
                # thread (the engine still serves the incumbent).
                slot["error"] = e
            finally:
                done.set()

    def _engine_loop_inner(self, eng, Request) -> None:
        # The loop's parts are ``serving.*`` trace annotations (no
        # records: an iteration may be 2 ms of sleep), so that a
        # device idle gap outside ``Engine.step`` has a name too.
        while not self._stop.is_set():
            with phase("serving.control"):
                self._run_control(eng)
            with phase("serving.mailbox"):
                self._admit_mailbox(eng, Request)
            # Dispatch BEFORE the idle check too: a drain command
            # finishes requests inside _run_control, and their
            # waiting clients must not hang on an idle engine.
            with phase("serving.dispatch"):
                self._dispatch_completed(eng)
            if eng.idle:
                with phase("serving.idle_sleep"):
                    time.sleep(0.002)
                continue
            eng.step()
            with phase("serving.dispatch"):
                self._dispatch_completed(eng)

    def _admit_mailbox(self, eng, Request) -> None:
        """Hand the engine what the HTTP threads left in the mailbox:
        listener first, then ``submit``."""
        with self._lock:
            incoming, self._mailbox = self._mailbox, []
        for rid, prompt, n, arrival, session, tenant in incoming:
            with self._lock:
                stream_q = self._streams.get(rid)
            if stream_q is not None:
                # Registered BEFORE submit, on the engine thread:
                # the first token cannot race its listener.
                eng.add_token_listener(
                    rid,
                    lambda tok, done, _q=stream_q:
                        _q.put(("token", tok)))
            try:
                eng.submit(Request(id=rid, prompt=prompt,
                                   max_new_tokens=n,
                                   arrival=arrival,
                                   session=session,
                                   tenant=tenant))
            except ValueError as e:
                # An invalid request answers ITS caller; it must
                # never take down the engine thread (and with it
                # every other in-flight request).
                eng.remove_token_listener(rid)
                with self._lock:
                    ev = self._events.pop(rid, None)
                    if ev is not None:
                        self._done[rid] = {"id": rid,
                                           "error": str(e)}
                        ev.set()
                    sq = self._streams.pop(rid, None)
                if sq is not None:
                    sq.put(("done", {"id": rid,
                                     "error": str(e)}))

    def _dispatch_completed(self, eng) -> None:
        if not eng.completed:
            return
        with self._lock:
            for rec in eng.completed:
                ev = self._events.pop(rec["id"], None)
                if ev is not None:
                    self._done[rec["id"]] = rec
                    ev.set()
                sq = self._streams.pop(rec["id"], None)
                if sq is not None:
                    sq.put(("done", rec))
        eng.completed.clear()

    def generate(self, prompt: np.ndarray, max_new_tokens: int,
                 timeout: float = 120.0,
                 session: str | None = None,
                 tenant: str = "default") -> dict:
        """Enqueue + wait (the HTTP handler path; also the in-process
        API tests use). ``session``: chat-session key — the engine
        retains the turn's KV pages under it and a follow-up call
        with the same key resumes with zero prefill for the retained
        history (serving/engine.py). ``tenant``: accounting label for
        the per-tenant latency histograms and trace records."""
        arrival = time.monotonic()
        ev = threading.Event()
        with self._lock:
            rid = f"http-{self._next_id}"
            self._next_id += 1
            self._events[rid] = ev
            self._mailbox.append((rid, np.array(prompt, np.int32),
                                  int(max_new_tokens), arrival,
                                  session, tenant))
        if not ev.wait(timeout):
            with self._lock:
                # Deregister so a late completion is dropped instead
                # of accumulating forever in _done.
                self._events.pop(rid, None)
                self._done.pop(rid, None)
            raise TimeoutError(f"request {rid} timed out")
        with self._lock:
            return self._done.pop(rid)

    def generate_stream(self, prompt: np.ndarray,
                        max_new_tokens: int,
                        timeout: float = 120.0,
                        session: str | None = None,
                        tenant: str = "default"):
        """Enqueue + yield per-token dicts as the engine produces
        them: ``{"token": N}`` per sampled token, then a final
        ``{"done": True, "tokens", "ttft_s", "latency_s"}``. The
        tokens flow engine thread → per-request queue → this
        generator, so a slow consumer never stalls decode.
        ``timeout`` bounds the wait for the NEXT item (the first
        token, which waits for the queue and the prompt's prefill, or
        the token after the last), not the whole request: a stream
        that delivers is alive however long its output (1,024 tokens
        from an engine whose 32 slots share 160 tokens/s are over 200
        s), and a wedged engine still frees the handler thread."""
        arrival = time.monotonic()
        q: queue.Queue = queue.Queue()
        with self._lock:
            rid = f"http-{self._next_id}"
            self._next_id += 1
            self._streams[rid] = q
            self._mailbox.append((rid, np.array(prompt, np.int32),
                                  int(max_new_tokens), arrival,
                                  session, tenant))
        deadline = time.monotonic() + timeout
        try:
            while True:
                try:
                    kind, val = q.get(
                        timeout=max(0.0,
                                    deadline - time.monotonic()))
                except queue.Empty:
                    raise TimeoutError(
                        f"request {rid} timed out mid-stream"
                    ) from None
                if kind == "token":
                    yield {"token": int(val)}
                    deadline = time.monotonic() + timeout
                    continue
                if "error" in val:
                    raise ValueError(val["error"])
                out = {"done": True, "tokens": val["tokens"],
                       "ttft_s": val["ttft_s"],
                       "latency_s": val["latency_s"]}
                if self.engine.model.cfg.vocab_size == 256:
                    out["text"] = bytes(
                        np.array(val["tokens"], np.uint8)).decode(
                            "utf-8", errors="replace")
                yield out
                return
        finally:
            # Runs on completion, timeout, AND abandonment (the
            # handler close()s the generator when the client
            # disconnects mid-stream): without the deregistration
            # the engine-side listener keeps filling an orphaned
            # queue until the sequence drains. Idempotent — the
            # engine loop pops both on normal completion too.
            with self._lock:
                self._streams.pop(rid, None)
            self.engine.remove_token_listener(rid)

    # -- HTTP --------------------------------------------------------------

    def _parse_generate(self, body: dict):
        """Validate a /generate body → (prompt_ids, max_new_tokens,
        session, tenant). Raises ValueError (the 400 path) BEFORE
        anything reaches the engine — the streaming handler needs
        every rejection to happen while the status line is still
        writable."""
        vocab = self.engine.model.cfg.vocab_size
        if "prompt_ids" in body:
            ids = np.array([int(t) for t in body["prompt_ids"]],
                             np.int32)
        elif "text" in body:
            if vocab != 256:
                raise ValueError(
                    "'text' prompts need a byte-vocab (256) model; "
                    "pass 'prompt_ids'")
            ids = np.frombuffer(
                body["text"].encode("utf-8"),
                dtype=np.uint8).astype(np.int32)
        else:
            raise ValueError("body needs 'prompt_ids' or 'text'")
        if ids.size == 0:
            raise ValueError("empty prompt")
        if ids.size and (ids.min() < 0 or ids.max() >= vocab):
            raise ValueError(f"prompt ids must be in [0, {vocab})")
        n = int(body.get("max_new_tokens", 16))
        limit = self.engine.cfg.max_seq_len
        if n < 1 or ids.size + n > limit:
            raise ValueError(
                f"prompt ({ids.size}) + max_new_tokens ({n}) must "
                f"fit max_seq_len ({limit})")
        session = body.get("session")
        if session is not None and not isinstance(session, str):
            raise ValueError("'session' must be a string key")
        tenant = body.get("tenant", "default")
        if not isinstance(tenant, str) or not tenant:
            raise ValueError("'tenant' must be a non-empty string")
        return ids, n, session, tenant

    def _handle_generate(self, body: dict) -> dict:
        ids, n, session, tenant = self._parse_generate(body)
        rec = self.generate(ids, n, session=session, tenant=tenant)
        if "error" in rec:
            raise ValueError(rec["error"])
        out = {"tokens": rec["tokens"], "ttft_s": rec["ttft_s"],
               "latency_s": rec["latency_s"]}
        if self.engine.model.cfg.vocab_size == 256:
            out["text"] = bytes(
                np.array(rec["tokens"], np.uint8)).decode(
                    "utf-8", errors="replace")
        return out

    def start(self) -> "ServingServer | None":
        from distributed_training_tpu.telemetry.metrics_server \
            import PROM_CONTENT_TYPE

        server = self

        class Handler(http.server.BaseHTTPRequestHandler):
            # Chunked transfer-encoding (the streaming path) is an
            # HTTP/1.1 construct; non-stream replies always carry
            # Content-Length, so keep-alive semantics stay valid.
            protocol_version = "HTTP/1.1"

            def _reply(self, code: int, payload: dict,
                       headers: tuple = ()) -> None:
                body = (json.dumps(payload) + "\n").encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in headers:
                    self.send_header(k, v)
                # One request per connection: clients here are
                # one-shot, and a dangling keep-alive socket at
                # server stop() surfaces as handler-thread noise.
                self.send_header("Connection", "close")
                self.close_connection = True
                self.end_headers()
                self.wfile.write(body)

            def _shed(self) -> dict | None:
                """Load-shedding gate for POST /generate: 503 +
                Retry-After while draining, after an engine crash,
                or past the configured queue depth — a bounded
                refusal beats queuing until the client times out."""
                eng = server.engine
                if server.engine_error is not None:
                    return {"error": "engine crashed: "
                                     + server.engine_error}
                if server.draining:
                    return {"error": "draining: not admitting new "
                                     "requests"}
                if server.max_queue_depth > 0:
                    with server._lock:
                        depth = (len(eng.queue)
                                 + len(server._mailbox))
                    if depth >= server.max_queue_depth:
                        return {"error": "queue full "
                                         f"(depth {depth} >= "
                                         f"{server.max_queue_depth})"}
                return None

            def _chunk(self, data: bytes) -> None:
                self.wfile.write(f"{len(data):X}\r\n".encode()
                                 + data + b"\r\n")
                self.wfile.flush()

            def _stream_generate(self, body: dict) -> None:
                try:
                    ids, n, session, tenant = \
                        server._parse_generate(body)
                except (ValueError, KeyError) as e:
                    self._reply(400, {"error": str(e)})
                    return
                gen = server.generate_stream(ids, n,
                                             session=session,
                                             tenant=tenant)
                self.send_response(200)
                self.send_header("Content-Type",
                                 "application/jsonl")
                self.send_header("Transfer-Encoding", "chunked")
                self.send_header("Connection", "close")
                self.close_connection = True
                self.end_headers()
                try:
                    for item in gen:
                        self._chunk((json.dumps(item) + "\n")
                                    .encode())
                except (ValueError, TimeoutError) as e:
                    # Headers are gone; the error becomes the
                    # stream's last line (best-effort — the client
                    # may already be gone).
                    try:
                        self._chunk((json.dumps(
                            {"error": str(e)}) + "\n").encode())
                    except OSError:
                        pass
                except OSError:
                    # Client disconnected mid-stream; nobody left
                    # to tell.
                    pass
                finally:
                    # close() reaches generate_stream's finally so
                    # the engine-side listener is deregistered even
                    # when the stream is abandoned.
                    gen.close()
                    try:
                        self.wfile.write(b"0\r\n\r\n")
                    except OSError:
                        pass

            def do_POST(self):  # noqa: N802 — http.server API
                if self.path.split("?")[0] != "/generate":
                    self._reply(404, {"error": "try POST /generate"})
                    return
                shed = self._shed()
                if shed is not None:
                    self._reply(503, shed, headers=(
                        ("Retry-After",
                         str(max(1, int(server.retry_after_s)))),))
                    return
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    body = json.loads(self.rfile.read(n) or b"{}")
                except (ValueError, KeyError) as e:
                    self._reply(400, {"error": str(e)})
                    return
                if body.get("stream"):
                    self._stream_generate(body)
                    return
                try:
                    self._reply(200, server._handle_generate(body))
                except (ValueError, KeyError) as e:
                    self._reply(400, {"error": str(e)})
                except TimeoutError as e:
                    self._reply(504, {"error": str(e)})

            def do_GET(self):  # noqa: N802 — http.server API
                path = self.path.split("?")[0]
                eng = server.engine
                if path == "/healthz":
                    # Tri-state: "unhealthy" (503) when the engine
                    # thread died, "draining" (200 — the pod is
                    # healthy, just not admitting) during a drain,
                    # else "ok".
                    alive = (server._engine_thread is not None
                             and server._engine_thread.is_alive())
                    if server.engine_error is not None or not alive:
                        status, code = "unhealthy", 503
                    elif server.draining:
                        status, code = "draining", 200
                    else:
                        status, code = "ok", 200
                    self._reply(code, {
                        "status": status,
                        "error": server.engine_error,
                        "in_flight": eng.in_flight,
                        "queue_depth": len(eng.queue),
                        "weights_version": eng.weights_version,
                        **eng.cache.occupancy()})
                    return
                if path == "/metrics":
                    body = server.metrics.render().encode()
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     PROM_CONTENT_TYPE)
                    self.send_header("Content-Length",
                                     str(len(body)))
                    self.send_header("Connection", "close")
                    self.close_connection = True
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if path == "/debug/requests":
                    self._reply(200, debug_requests_snapshot(eng))
                    return
                self._reply(404, {"error": "try /healthz, /metrics "
                                           "or /debug/requests"})

            def log_message(self, fmt, *args):
                logger.debug("serving http: " + fmt, *args)

        try:
            self._httpd = http.server.ThreadingHTTPServer(
                ("0.0.0.0", self._requested_port), Handler)
        except OSError as e:
            logger.warning("serving endpoint NOT started (port %s): "
                           "%s", self._requested_port, e)
            return None
        self.port = self._httpd.server_address[1]
        if self._metrics_owns_port:
            self.metrics.start()
        else:
            # Renderer-only mode: no second socket, but the observer
            # must still fold records so GET /metrics on THIS port
            # has data (MetricsServer.start() normally registers it
            # post-bind). The engine emits through the AMBIENT sink
            # when none was passed explicitly, so observe that one;
            # the disabled default sink never calls observers, which
            # degrades to an empty (but valid) exposition.
            from distributed_training_tpu.telemetry import current
            tel = self._telemetry if self._telemetry is not None \
                else current()
            tel.add_observer(self.metrics.observe)
        self._engine_thread = threading.Thread(
            target=self._engine_loop, name="serving-engine",
            daemon=True)
        self._engine_thread.start()
        self._http_thread = threading.Thread(
            target=self._httpd.serve_forever, name="serving-http",
            daemon=True)
        self._http_thread.start()
        logger.info("serving endpoint on :%d (POST /generate)",
                    self.port)
        return self

    def stop(self) -> None:
        """Stop the HTTP front + engine thread. Thread joins carry a
        5 s timeout — a wedged engine step must not hang teardown —
        but a straggler is COUNTED, not silently leaked: the
        ``serving_stop`` telemetry event reports ``leaked_threads``
        (0 after every clean stop, pinned by test) so a leak shows in
        the stream instead of as mystery state in the next test."""
        from distributed_training_tpu import telemetry as tel

        self._stop.set()
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        if self.metrics is not None:
            self.metrics.stop()
        leaked = []
        for t in (self._engine_thread, self._http_thread):
            if t is not None:
                t.join(timeout=5)
                if t.is_alive():
                    leaked.append(t.name)
        self.leaked_threads = len(leaked)
        if leaked:
            logger.warning("serving stop leaked %d thread(s): %s",
                           len(leaked), ", ".join(leaked))
        tel.event("serving_stop", leaked_threads=len(leaked),
                  leaked=leaked,
                  engine_error=self.engine_error)
        self._engine_thread = self._http_thread = None


def engine_config_from_yaml(plan, engine_block: dict):
    """conf/serving/*.yaml ``engine:`` block → EngineConfig, with 0
    meaning "take the plan's value" (engine_config_for_plan)."""
    import dataclasses

    from distributed_training_tpu.serving.disagg import (
        engine_config_for_plan)

    base = engine_config_for_plan(
        plan,
        page_size=int(engine_block.get("page_size", 16)),
        prefill_chunk=int(engine_block.get("prefill_chunk", 16)))
    # 0 / empty = "keep the plan-derived value" for every knob
    # (temperature 0 IS the plan-derived greedy default;
    # prefill_slots 0 means "same table as max_batch" and spec_k 1
    # is plain one-token decode, so both pass through replace()
    # harmlessly when set).
    over = {k: v for k, v in engine_block.items()
            if k in ("max_batch", "num_pages", "max_seq_len",
                     "temperature", "top_k", "prefill_slots",
                     "spec_k", "resident_k", "eos_id")
            and v not in (0, 0.0, None, "")}
    # prefix_sharing is a REAL boolean: False == 0 would fall into
    # the "keep default" filter above and silently re-enable it.
    if "prefix_sharing" in engine_block \
            and engine_block["prefix_sharing"] is not None:
        over["prefix_sharing"] = bool(engine_block["prefix_sharing"])
    # swap_staleness_tokens: 0 is a MEANINGFUL bound (resubmit every
    # in-flight request at swap time), so it must dodge the 0-filter;
    # -1/absent = unbounded.
    if "swap_staleness_tokens" in engine_block \
            and engine_block["swap_staleness_tokens"] is not None:
        over["swap_staleness_tokens"] = int(
            engine_block["swap_staleness_tokens"])
    return dataclasses.replace(base, **over)


def build_server(artifact: str, plan_name: str, port: int = 0,
                 metrics_port: int | None = None,
                 telemetry=None,
                 engine_block: dict | None = None,
                 server_block: dict | None = None) -> ServingServer:
    """Artifact + committed plan → laid-out engine → server.

    The provenance gate lives in WeightStore: an artifact whose
    recorded source plan no longer matches its committed fingerprint
    refuses to serve (serving/disagg.py)."""
    import jax

    from distributed_training_tpu.parallel.planner import (
        load_plan, model_for_plan)
    from distributed_training_tpu.runtime import build_mesh, MeshSpec
    from distributed_training_tpu.serving.disagg import WeightStore
    from distributed_training_tpu.serving.engine import Engine

    plan = load_plan(plan_name)
    store = WeightStore(artifact)
    model = model_for_plan(plan)
    spec = MeshSpec(**{a: plan.mesh.get(a, 1)
                       for a in ("pp", "dp", "fsdp", "sp", "tp")})
    mesh = build_mesh(spec, jax.devices()[:spec.total])
    ecfg = engine_config_from_yaml(plan, engine_block or {})
    engine = Engine(model, store.params_for(mesh, plan), ecfg,
                    mesh=mesh,
                    weights_provenance=store.provenance)
    engine.warmup()
    sb = server_block or {}
    return ServingServer(
        engine, port=port, metrics_port=metrics_port,
        telemetry=telemetry,
        max_queue_depth=int(sb.get("max_queue_depth", 0) or 0),
        retry_after_s=float(sb.get("retry_after_s", 1.0) or 1.0),
        incident_dir=sb.get("incident_dir"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m distributed_training_tpu.serving.server",
        description="Continuous-batching inference server.")
    ap.add_argument("--artifact", required=True,
                    help="consolidated export (checkpoint/export.py)")
    ap.add_argument("--plan", default=None,
                    help="committed decode plan name (conf/plans/); "
                         "default: the --config file's plan")
    ap.add_argument("--config", default=None,
                    help="serving YAML (conf/serving/default.yaml): "
                         "engine geometry, ports; "
                         "explicit flags win per key")
    ap.add_argument("--port", type=int, default=None)
    ap.add_argument("--metrics-port", type=int, default=None)
    args = ap.parse_args(argv)

    conf: dict = {}
    if args.config:
        import yaml
        with open(args.config) as f:
            conf = yaml.safe_load(f) or {}
    plan_name = args.plan or conf.get("plan")
    if not plan_name:
        ap.error("no plan: pass --plan or a --config with one")
    srv_conf = conf.get("server") or {}
    port = args.port if args.port is not None \
        else int(srv_conf.get("port", 8100))
    mp_conf = srv_conf.get("metrics_port", 8101)
    # metrics_port: null in the config = no standalone endpoint; the
    # serving port's own GET /metrics still works (renderer-only).
    metrics_port = args.metrics_port if args.metrics_port is not None \
        else (int(mp_conf) if mp_conf is not None else None)

    import os

    from distributed_training_tpu.runtime import enable_compile_cache
    from distributed_training_tpu.telemetry import (Telemetry,
                                                    install)
    enable_compile_cache()
    # The sink must be ENABLED (jsonl-backed) for the observer chain
    # to fire — a disabled Telemetry emits nothing and the gauges
    # would stay empty (telemetry/events.py::_emit's fast path).
    tel = install(Telemetry(events_jsonl=os.path.join(
        "outputs", "serving", "events.jsonl")))
    if not srv_conf.get("incident_dir"):
        srv_conf = {**srv_conf,
                    "incident_dir": os.path.join(
                        "outputs", "serving", "incidents")}
    srv = build_server(args.artifact, plan_name, port=port,
                       metrics_port=metrics_port, telemetry=tel,
                       engine_block=conf.get("engine") or {},
                       server_block=srv_conf)
    if srv.start() is None:
        return 1
    print(f"serving on :{srv.port} (metrics :{metrics_port}); "
          "Ctrl-C to stop")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        srv.stop()
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
