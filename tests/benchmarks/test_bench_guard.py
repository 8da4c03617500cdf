"""Teardown: after `benchmarks/run.py` ends — normally, with the cell
killed, with the node daemon killed first, or on SIGTERM — no process
it started is alive.  CPU rehearsal sizes; every case boots a cluster,
so they are few and short."""

import os
import signal
import subprocess
import sys
import time
import uuid

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = os.path.join(REPO, "benchmarks", "run.py")
MARK_ENV = "RT_BENCH_RUN_MARK"


def marked(mark: str) -> dict:
    """pid -> command line of every process whose environment carries
    the run's marker."""
    needle = f"{MARK_ENV}={mark}".encode()
    out = {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) == os.getpid():
            continue
        try:
            with open(f"/proc/{entry}/environ", "rb") as f:
                if needle not in f.read():
                    continue
            with open(f"/proc/{entry}/cmdline", "rb") as f:
                out[int(entry)] = f.read().replace(b"\0", b" ").decode()
        except OSError:
            continue
    return out


def in_session(sid: int) -> list:
    out = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                stat = f.read()
        except OSError:
            continue
        if int(stat[stat.rfind(")") + 2:].split()[3]) == sid:
            out.append(int(entry))
    return out


def start(workload: str, *extra):
    mark = "t" + uuid.uuid4().hex
    env = {**os.environ, MARK_ENV: mark, "RT_BENCH_DEADLINE_S": "240"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.Popen(
        [sys.executable, RUN, "--workload", workload, "--rehearse",
         "--seed", "7", *extra],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    return proc, mark


def wait_for(mark: str, what: str, timeout: float = 120.0) -> int:
    """pid of the first marked process whose command line holds `what`."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        for pid, cmd in marked(mark).items():
            if what in cmd:
                return pid
        time.sleep(0.1)
    raise AssertionError(f"no process with {what!r} showed up: {marked(mark)}")


def assert_clean(mark: str, cell_pid=None):
    left = marked(mark)
    assert not left, f"processes outlived the run: {left}"
    if cell_pid is not None:
        assert not in_session(cell_pid), "the run's session is not empty"


@pytest.mark.parametrize("workload", ["mistral7b_chat_open",
                                      "gpt2m_train_stream"])
def test_rehearsal_leaves_nothing_and_prints_no_result(workload):
    proc, mark = start(workload)
    out, err = proc.communicate(timeout=300)
    assert proc.returncode == 3, err[-3000:]
    assert "rehearsal passed" in err
    # never a result line from a run without a chip
    assert '"correct"' not in out.strip().splitlines()[-1]
    assert '"metrics"' not in out
    assert_clean(mark)


def test_the_train_window_counts_every_step_it_sent_over_all_of_its_time(
        tmp_path):
    """The train loop keeps `ahead_steps` steps in flight: when the
    window's time is up it sends nothing more, waits for every step it
    sent and reads the clock after that wait, so no step is counted
    that has not ended and none that was sent is left out."""
    import json

    detail = tmp_path / "detail.json"
    proc, mark = start("gpt2m_train_stream", "--detail", str(detail))
    out, err = proc.communicate(timeout=300)
    assert proc.returncode == 3, err[-3000:]
    assert_clean(mark)
    with open(detail) as f:
        d = json.load(f)
    ctx, t = d["ctx"], d["ctx"]["train"]
    ahead, ends = t["ahead"], t["ends_s"]
    assert ahead == ctx["traffic"]["ahead_steps"] >= 1
    assert t["steps"] == len(ends) == len(t["spans"]["step"])
    assert t["steps"] == ctx["reported_steps"] > ahead
    assert ends == sorted(ends)
    # the steps still in flight when the time was up ended after it,
    # and the clock was read after the last of them
    assert ends[-ahead - 1] <= ctx["seconds"] + 1.0
    assert ctx["seconds"] <= ends[-1] <= t["elapsed_s"]
    assert d["e2e"]["train_tokens_per_s"] == pytest.approx(
        t["steps"] * t["tokens_per_step"] / t["elapsed_s"])
    # a step's span is never counted twice: the spans fit in the time
    assert sum(t["spans"]["step"]) <= t["elapsed_s"]


def test_cell_killed_mid_run_leaves_nothing():
    proc, mark = start("mistral7b_chat_open", "--seconds", "60")
    cell = wait_for(mark, "benchmarks.cell")
    wait_for(mark, "worker_main")
    time.sleep(3.0)
    os.kill(cell, signal.SIGKILL)
    out, err = proc.communicate(timeout=120)
    assert proc.returncode != 0
    assert '"correct": true' not in out
    assert_clean(mark, cell)


def test_daemon_killed_first_orphans_are_swept():
    proc, mark = start("gpt2m_train_stream", "--seconds", "60")
    cell = wait_for(mark, "benchmarks.cell")
    daemon = wait_for(mark, "noded")
    wait_for(mark, "worker_main")
    time.sleep(3.0)
    os.kill(daemon, signal.SIGKILL)   # workers are orphans now
    time.sleep(1.0)
    try:  # the driver may already have given up on its dead daemon
        os.kill(cell, signal.SIGKILL)
    except ProcessLookupError:
        pass
    out, err = proc.communicate(timeout=120)
    assert proc.returncode != 0
    assert_clean(mark, cell)


def test_guard_sigterm_ends_the_run_and_leaves_nothing():
    proc, mark = start("mistral7b_chat_open", "--seconds", "60")
    cell = wait_for(mark, "benchmarks.cell")
    wait_for(mark, "worker_main")
    time.sleep(3.0)
    proc.send_signal(signal.SIGTERM)
    out, err = proc.communicate(timeout=120)
    assert proc.returncode == 128 + signal.SIGTERM
    assert '"correct"' not in out
    assert_clean(mark, cell)


def test_guard_deadline_ends_a_hung_cell(tmp_path):
    """The guard's own deadline: a cell that never ends is killed."""
    mark = "t" + uuid.uuid4().hex
    env = {**os.environ, MARK_ENV: mark, "RT_BENCH_DEADLINE_S": "6"}
    proc = subprocess.Popen(
        [sys.executable, RUN, "--workload", "mistral7b_chat_open",
         "--rehearse", "--seconds", "120"],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    out, err = proc.communicate(timeout=120)
    assert proc.returncode == 124
    assert "deadline" in err
    assert_clean(mark)


def test_without_a_chip_the_command_fails_and_prints_no_result():
    env = {k: v for k, v in os.environ.items() if k != "RT_TPU_CHIPS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", "mistral7b_chat_open", "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert proc.stdout.strip() == ""
