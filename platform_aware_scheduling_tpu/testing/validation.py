"""In-cluster validation runner: the service as a coverage-instrumented
process with a /prestop hook.

Capability parity with the reference's validation build
(reference gpu-aware-scheduling/pkg/gpuscheduler/validation_test.go:1-68):
the Go version wraps main() in a test binary so it can run *in a real
cluster with coverage instrumentation*, terminated via an HTTP prestop
hook on port 8088 that lets the coverage profile flush.

Python equivalent::

    coverage run -m platform_aware_scheduling_tpu.testing.validation tas \
        --unsafe --port 9001

A container preStop hook (or operator) then calls
``GET http://localhost:8088/prestop``; the runner shuts the service down
cleanly so ``coverage`` writes its data file.
"""

from __future__ import annotations

import sys
import threading
from http.server import BaseHTTPRequestHandler, HTTPServer

PRESTOP_PORT = 8088


def serve_prestop(trigger: threading.Event, port: int = PRESTOP_PORT) -> HTTPServer:
    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):
            if self.path == "/prestop":
                self.send_response(200)
                self.end_headers()
                self.wfile.write(b"stopping\n")
                trigger.set()
            else:
                self.send_response(404)
                self.end_headers()

        do_POST = do_GET

        def log_message(self, fmt, *args):
            pass

    server = HTTPServer(("127.0.0.1", port), Handler)
    threading.Thread(
        target=server.serve_forever, name="pas-prestop", daemon=True
    ).start()
    return server


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] not in ("tas", "gas"):
        print("usage: validation {tas|gas} [service flags...]", file=sys.stderr)
        return 2
    which, rest = argv[0], argv[1:]

    import signal

    stop = threading.Event()
    prestop = serve_prestop(stop)

    if which == "tas":
        from platform_aware_scheduling_tpu.cmd import tas as svc
    else:
        from platform_aware_scheduling_tpu.cmd import gas as svc

    result = [0]
    thread = threading.Thread(
        target=lambda: result.__setitem__(0, svc.main(rest)),
        name="pas-main",
        daemon=True,
    )
    thread.start()
    stop.wait()
    # deliver the service's own shutdown path (it waits on SIGINT/SIGTERM)
    signal.raise_signal(signal.SIGTERM)
    thread.join(timeout=10)
    prestop.shutdown()
    return result[0]


if __name__ == "__main__":
    raise SystemExit(main())
