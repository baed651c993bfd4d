"""A benchmark run leaves nothing behind, and the program starts no process.

The ledger's PR 36 line: a run of an accepted cell left a process running
after its result line (``process_left_running``), so every later run
could have been served by it.  Here a run is started in a session of its
own; once it has printed its last line it must be gone within seconds,
and ``/proc`` must hold no process of that session: no worker pool, no
helper, no non-daemon thread that keeps the interpreter from ending.
The static half: nothing under ``oceanbase_tpu/`` imports a module that
starts processes, outside the two files that say why they may; one of
them, ``native.py``, does start one on a fresh checkout's first run, and
its build is run here the same way.
"""

import ast
import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: seconds a run may live on after its last line
GONE_WITHIN_S = 20.0

#: the modules that start processes, and the only files that may import
#: one (with why)
PROCESS_MODULES = ("multiprocessing", "subprocess",
                   "concurrent.futures.process")
MAY_START_A_PROCESS = {
    # ``openssl`` once, to mint a self-signed certificate when TLS is
    # turned on and none is configured: no benchmark configuration does
    "server/tls.py",
    # ``make`` once, when native/libobtpu_native.so is missing, as it is
    # in a fresh checkout (``.gitignore`` lists it): the first crc64 of a
    # run's load builds it, ON the run's path.  ``subprocess.run`` waits
    # for it; the test below shows that nothing of it is left
    "native.py",
}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """The benchmark's files in a directory of their own (a run keeps
    its scratch beside them); the program comes from this checkout."""
    root = tmp_path_factory.mktemp("bench_checkout")
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns(".scratch", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    return str(root)


def _left_of_the_run(sid: int, mark: str) -> list[str]:
    """``pid (comm)`` of every live process that is in session ``sid`` or
    carries ``mark`` in its environment (a descendant that made a session
    of its own still inherited the run's environment)."""
    out = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit() or int(pid) == os.getpid():
            continue
        try:
            with open(f"/proc/{pid}/stat", encoding="utf-8",
                      errors="replace") as f:
                stat = f.read()
            with open(f"/proc/{pid}/environ", "rb") as f:
                marked = mark.encode() in f.read()
        except OSError:
            continue            # it ended while we looked, or is not ours
        comm_end = stat.rindex(")")
        fields = stat[comm_end + 2:].split()
        # after the command: state, ppid, pgrp, session, ...
        if fields[0] != "Z" and (int(fields[3]) == sid or marked):
            out.append(f"{pid} {stat[stat.index('('):comm_end + 1]}")
    return out


def _run_in_own_session(checkout: str, argv: list[str]):
    """-> (exit code, seconds from the last line to the exit, the lines,
    what is left of the session)."""
    mark = f"OBTPU_RUN_MARK_{os.getpid()}_{time.monotonic_ns()}"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               OBTPU_RUN_MARK=mark)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.Popen(
        [sys.executable] + argv, cwd=checkout, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        start_new_session=True)
    lines, last_at = [], time.monotonic()
    try:
        for line in proc.stdout:
            lines.append(line)
            last_at = time.monotonic()
        # the pipe closed: every holder of it has ended or closed it
        rc = proc.wait(timeout=GONE_WITHIN_S + 5)
        gone_after = time.monotonic() - last_at
        left = _left_of_the_run(proc.pid, mark)
    finally:
        if proc.poll() is None:
            proc.kill()
        try:
            os.killpg(proc.pid, 9)      # whatever the session still holds
        except OSError:
            pass
    return rc, gone_after, lines, left


@pytest.mark.parametrize("label,argv,want_rc", [
    ("scan", ["benchmark/run.py", "--workload", "tpch_sf1.scan",
              "--seed", "3800000011", "--seconds", "2", "--trace", "0",
              "--rehearse", "0.01"], 3),
    ("sf10", ["benchmark/run.py", "--workload", "tpch_sf10.q1q6q14",
              "--seed", "3800000012", "--seconds", "2", "--trace", "0",
              "--rehearse", "0.01"], 3),
    # a limit so short that the run ends itself (exit code 4), the
    # reference child stopped by the watchdog
    ("ends_itself", ["benchmark/tests/drive_short_limit.py", "6",
                     "--workload", "tpch_sf10.q1q6q14", "--seed",
                     "3800000013", "--seconds", "30", "--trace", "0",
                     "--rehearse", "0.01"], 4),
])
def test_a_run_that_has_printed_its_last_line_is_gone(checkout, label,
                                                      argv, want_rc):
    rc, gone_after, lines, left = _run_in_own_session(checkout, argv)
    assert rc == want_rc, "".join(lines[-5:])
    assert lines, "the run printed nothing"
    if want_rc == 3:
        assert '"rehearsal": true' in lines[-1]
    else:
        assert '"over_budget"' in lines[-1]
    assert gone_after < GONE_WITHIN_S, gone_after
    assert left == [], left


def _imports(tree: ast.AST):
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module
            for alias in node.names:
                yield f"{node.module}.{alias.name}"


def test_the_program_imports_nothing_that_starts_a_process():
    package = os.path.join(REPO, "oceanbase_tpu")
    found = []
    for directory, _dirs, files in os.walk(package):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(directory, name)
            rel = os.path.relpath(path, package)
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), filename=path)
            for module in _imports(tree):
                if any(module == m or module.startswith(m + ".")
                       for m in PROCESS_MODULES) \
                        or module == "concurrent.futures.ProcessPoolExecutor":
                    found.append((rel, module))
    assert {rel for rel, _m in found} <= MAY_START_A_PROCESS, found


_BUILD_AND_USE = """
import os, sys
import oceanbase_tpu.native as native
native._NATIVE_DIR = sys.argv[1]
native._SO = os.path.join(sys.argv[1], "libobtpu_native.so")
assert not os.path.exists(native._SO)
print("crc", native.crc64(b"a run's first checksum"))
print("built", os.path.isfile(native._SO), native.native_available())
"""


@pytest.mark.skipif(shutil.which("make") is None
                    or shutil.which("g++") is None,
                    reason="no toolchain: native.py falls back to NumPy")
def test_the_native_build_is_waited_for_and_leaves_no_process(tmp_path):
    """What a fresh checkout's first run does: the library is not there,
    ``native.py`` runs ``make``, and by the time the checksum is back the
    library is built and no process of the build is alive."""
    native = tmp_path / "native"
    shutil.copytree(os.path.join(REPO, "native"), native,
                    ignore=shutil.ignore_patterns("*.so", "*.tmp"))
    rc, gone_after, lines, left = _run_in_own_session(
        str(tmp_path), ["-c", _BUILD_AND_USE, str(native)])
    assert rc == 0, lines
    assert lines[-1].split() == ["built", "True", "True"], lines
    assert gone_after < GONE_WITHIN_S, gone_after
    assert left == [], left
