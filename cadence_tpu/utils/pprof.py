"""Diagnostic/profiling HTTP surface.

Reference: common/pprof.go starts Go's net/http/pprof endpoint per
service (config Service.PProf.Port). The Python/JAX equivalents served
here, all stdlib, no deps:

  GET /debug/pprof/            index
  GET /debug/pprof/stack       every thread's current stack (the
                               goroutine-profile analog)
  GET /debug/pprof/profile?seconds=N&hz=H
                               statistical CPU profile: samples all
                               thread stacks at H hz for N seconds and
                               returns collapsed stacks ("frame;frame N"
                               lines — feed straight to flamegraph.pl)
  GET /debug/pprof/heap?topn=N tracemalloc top allocation sites
                               (tracemalloc starts on first call)
  GET /debug/pprof/traces[?trace_id=ID]
                               the tracing flight recorder
                               (utils/tracing.py ring buffer) as
                               Chrome-trace-format JSON — load in
                               Perfetto / chrome://tracing; trace_id
                               filters to one request's trace
  POST /debug/pprof/device/start?dir=D
  POST /debug/pprof/device/stop
                               bracket a jax.profiler trace (XLA/TPU
                               device timeline, viewable in
                               tensorboard/xprof) — the device-side
                               story Go pprof has no equivalent for;
                               while it runs, every sampled span
                               (utils/tracing.py) is annotated into it
                               under its own name

The sampler is safe to run in production: it reads
``sys._current_frames`` from a daemon thread, never stops the world.
"""

from __future__ import annotations

import sys
import threading
import time
import traceback
from collections import Counter
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional, Tuple
from urllib.parse import parse_qs, urlparse

from cadence_tpu.utils.log import get_logger


def thread_stacks() -> str:
    """Every live thread's stack, most recent call last."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for ident, frame in sys._current_frames().items():
        out.append(
            f"--- thread {names.get(ident, '?')} (id {ident}) ---\n"
            + "".join(traceback.format_stack(frame))
        )
    return "\n".join(out)


def sample_cpu(seconds: float = 5.0, hz: float = 100.0) -> str:
    """Collapsed-stack statistical profile of all threads.

    Lines are ``frame;frame;...;frame count`` with the root first —
    flamegraph.pl / speedscope both ingest this directly.
    """
    me = threading.get_ident()
    counts: Counter = Counter()
    interval = 1.0 / max(hz, 1.0)
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        for ident, frame in sys._current_frames().items():
            if ident == me:
                continue
            stack = []
            f = frame
            while f is not None:
                code = f.f_code
                stack.append(f"{code.co_name} ({code.co_filename}:{f.f_lineno})")
                f = f.f_back
            counts[";".join(reversed(stack))] += 1
        time.sleep(interval)
    return "\n".join(f"{k} {v}" for k, v in counts.most_common())


def heap_top(topn: int = 30) -> str:
    import tracemalloc

    if not tracemalloc.is_tracing():
        tracemalloc.start()
        return (
            "tracemalloc started; allocations are tracked from now — "
            "call again for a snapshot"
        )
    snap = tracemalloc.take_snapshot()
    stats = snap.statistics("lineno")[:topn]
    total = sum(s.size for s in snap.statistics("filename"))
    lines = [f"total tracked: {total / 1e6:.1f} MB"]
    lines += [str(s) for s in stats]
    return "\n".join(lines)


class _Handler(BaseHTTPRequestHandler):
    server_version = "cadence-tpu-pprof"

    def log_message(self, fmt, *args):  # route to our logger, not stderr
        self.server._log.info("pprof " + fmt % args)

    def _reply(self, code: int, body: str,
               content_type: str = "text/plain; charset=utf-8") -> None:
        data = body.encode()
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def _route(self) -> Tuple[str, dict]:
        u = urlparse(self.path)
        return u.path.rstrip("/"), parse_qs(u.query)

    def do_GET(self) -> None:  # noqa: N802 (stdlib casing)
        path, q = self._route()
        try:
            if path in ("", "/debug/pprof"):
                self._reply(200, __doc__ or "")
            elif path == "/debug/pprof/stack":
                self._reply(200, thread_stacks())
            elif path == "/debug/pprof/profile":
                seconds = float(q.get("seconds", ["5"])[0])
                hz = float(q.get("hz", ["100"])[0])
                # clamp like seconds: an absurd hz would busy-spin a
                # core walking every thread's stack for the whole window
                self._reply(200, sample_cpu(
                    min(seconds, 120.0), min(hz, 1000.0)
                ))
            elif path == "/debug/pprof/heap":
                self._reply(200, heap_top(int(q.get("topn", ["30"])[0])))
            elif path == "/debug/pprof/traces":
                from cadence_tpu.utils.tracing import TRACER

                trace_id = q.get("trace_id", [None])[0]
                self._reply(
                    200, TRACER.chrome_trace_json(trace_id),
                    content_type="application/json",
                )
            else:
                self._reply(404, f"unknown pprof path {path}\n")
        except Exception as e:  # diagnostics must not kill the server
            self._reply(500, f"{type(e).__name__}: {e}\n")

    def do_POST(self) -> None:  # noqa: N802
        path, q = self._route()
        try:
            if path == "/debug/pprof/device/start":
                import jax

                from cadence_tpu.utils.tracing import TRACER

                trace_dir = q.get("dir", ["/tmp/cadence-tpu-trace"])[0]
                jax.profiler.start_trace(trace_dir)
                TRACER.set_profiler_prefix("")
                self._reply(200, f"device trace started -> {trace_dir}\n")
            elif path == "/debug/pprof/device/stop":
                import jax

                from cadence_tpu.utils.tracing import TRACER

                TRACER.set_profiler_prefix(None)
                jax.profiler.stop_trace()
                self._reply(200, "device trace stopped\n")
            else:
                self._reply(404, f"unknown pprof path {path}\n")
        except Exception as e:
            self._reply(500, f"{type(e).__name__}: {e}\n")


class PProfServer:
    """The per-process diagnostics endpoint (common/pprof.go Start)."""

    def __init__(self, port: int = 0, host: str = "127.0.0.1") -> None:
        self._log = get_logger("cadence_tpu.pprof")
        self._httpd = ThreadingHTTPServer((host, port), _Handler)
        self._httpd._log = self._log
        self._httpd.daemon_threads = True
        self._thread: Optional[threading.Thread] = None

    @property
    def address(self) -> str:
        host, port = self._httpd.server_address[:2]
        return f"{host}:{port}"

    def start(self) -> "PProfServer":
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="pprof", daemon=True
        )
        self._thread.start()
        self._log.info(f"pprof listening on {self.address}")
        return self

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread:
            self._thread.join(timeout=5.0)
