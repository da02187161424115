"""The program under test as a child process: `python -m imaginary_tpu`.

The child is the only process that opens the chip; this process never
initialises a JAX backend while it runs. Lifecycle as chip_smoke.py: a
session of its own, SIGTERM to the group, SIGKILL after a grace, and a
wait until it has ended.
"""

from __future__ import annotations

import os
import signal
import socket
import subprocess
import sys
import sysconfig
import time

from harness import health

BOOT_TIMEOUT_S = 240.0
NATIVE_MODULES = (("_imaginary_codecs", "codecs.cpp"), ("_imaginary_entropy", "entropy.cpp"))


class BenchFailure(Exception):
    """A run that cannot produce a result: it exits nonzero and prints none."""


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def ensure_native(root: str) -> float:
    """Build the program's native codecs when a .so is missing or older
    than its source; a fresh checkout pays this once. Returns seconds."""
    native = os.path.join(root, "imaginary_tpu", "native")
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    stale = []
    for mod, src in NATIVE_MODULES:
        so, cpp = os.path.join(native, mod + suffix), os.path.join(native, src)
        if not os.path.exists(cpp):
            raise BenchFailure(f"the program is missing: no {cpp}")
        if not os.path.exists(so) or os.path.getmtime(so) < os.path.getmtime(cpp):
            stale.append(mod)
    if not stale:
        return 0.0
    t0 = time.monotonic()
    r = subprocess.run([sys.executable, "-m", "imaginary_tpu.native.build"], cwd=root,
                       capture_output=True, text=True, timeout=600)
    if r.returncode != 0:
        raise BenchFailure(f"native build failed ({r.returncode}): {(r.stderr or r.stdout)[-2000:]}")
    return time.monotonic() - t0


class Server:
    """`python -m imaginary_tpu --port <free> <args>`, output to `log_path`."""

    # how the interpreter is told to start the program (a test plants a
    # fault in the program by starting it through a patch instead)
    launcher = ("-m", "imaginary_tpu")

    def __init__(self, root: str, args: list, env: dict, log_path: str):
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.log_path = log_path
        self._log = open(log_path, "wb")
        cmd = [sys.executable, *self.launcher, "--port", str(self.port), *args]
        self.proc = subprocess.Popen(cmd, cwd=root, env=env, stdout=self._log,
                                     stderr=subprocess.STDOUT, start_new_session=True)

    def log_tail(self, n: int = 3000) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            return f.read().decode(errors="replace")[-n:]

    def wait_healthy(self, timeout_s: float = BOOT_TIMEOUT_S) -> dict:
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout_s:
            if self.proc.poll() is not None:
                raise BenchFailure(f"server exited {self.proc.returncode} before serving:\n"
                                   f"{self.log_tail()}")
            try:
                return health.fetch(self.base, timeout=2.0)
            except (OSError, ValueError):
                time.sleep(0.25)
        raise BenchFailure(f"server not healthy after {timeout_s:.0f}s:\n{self.log_tail()}")

    def stop(self) -> None:
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGTERM)
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                os.killpg(self.proc.pid, signal.SIGKILL)
                self.proc.wait()
            except ProcessLookupError:
                self.proc.wait()
        # a worker the server forked may outlive its parent: end the group
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        if not self._log.closed:
            self._log.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
