"""The parent's end of the pipe to the load generator's process."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Any, Dict

from harness.spec import PERF_DIR, ROOT


class ClientError(RuntimeError):
    """The client process reported an error or went away."""


class ClientProcess:
    """``perf/loadgen/client.py`` as a child: one JSON object a line each
    way.  The child is forced off the accelerator and never imports JAX, so
    the parent keeps the chip."""

    def __init__(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(PERF_DIR, "loadgen", "client.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, cwd=ROOT, env=env,
            text=True, bufsize=1)

    def send(self, cmd: Dict[str, Any]) -> None:
        try:
            self.proc.stdin.write(json.dumps(cmd) + "\n")
            self.proc.stdin.flush()
        except (BrokenPipeError, ValueError) as e:
            raise ClientError(f"client process is gone: {e}") from e

    def read(self) -> Dict[str, Any]:
        line = self.proc.stdout.readline()
        if not line:
            raise ClientError(
                f"client process ended (exit {self.proc.poll()})")
        msg = json.loads(line)
        if "error" in msg and "event" not in msg and "results" not in msg:
            raise ClientError(msg["error"])
        return msg

    def call(self, cmd: Dict[str, Any]) -> Dict[str, Any]:
        self.send(cmd)
        return self.read()

    def expect(self, event: str) -> Dict[str, Any]:
        msg = self.read()
        if msg.get("event") != event:
            raise ClientError(f"expected {event!r} from the client, got "
                              f"{str(msg)[:300]}")
        return msg

    def close(self) -> None:
        """Ask the child to quit, wait for it, kill it if it will not."""
        if self.proc.poll() is None:
            try:
                self.send({"op": "quit"})
            except ClientError:
                pass
            try:
                self.proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        for pipe in (self.proc.stdin, self.proc.stdout):
            if pipe is not None:
                pipe.close()
