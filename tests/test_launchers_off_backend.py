"""One process per chip: a launcher never initializes a jax backend.

A TPU chip belongs to one process, so a role that starts device-holding
children (``spawn_ranks``, ``fleet_main`` local/ps_fleet, the
``serve_bench`` parent) must not have touched jax when it spawns them, and
the roles that only talk to such processes (router, drain) must not touch
it at all. The probe runs every role in ONE fresh interpreter (this pytest
process initialized its backend long ago) with ``subprocess.Popen``
replaced by a recorder that notes, at each spawn, whether
``jax._src.xla_bridge._backends`` is still empty.
"""

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = r"""
import importlib.util, json, os, subprocess, sys, tempfile

from jax._src import xla_bridge

spawns = []           # (role of the spawned command, backend still empty?)


class RecordingPopen:
    # Stands in for every child: records the launcher's backend state at
    # the spawn, "writes" a router's address file, and has already exited.
    pid = 0
    returncode = 0

    def __init__(self, cmd, **kwargs):
        role = next((a.split("=", 1)[1] for a in cmd
                     if a.startswith("-fleet_role=")),
                    next((a for a in cmd if a.startswith("multiverso_tpu.")),
                         "?"))
        spawns.append((role, xla_bridge._backends == {}))
        for a in cmd:
            if a.startswith("-fleet_addr_file="):
                with open(a.split("=", 1)[1], "w") as f:
                    f.write("127.0.0.1:1")      # nobody listens there

    def poll(self):
        return 0

    def wait(self, timeout=None):
        return 0

    def send_signal(self, sig):
        pass

    terminate = kill = lambda self: None


subprocess.Popen = RecordingPopen
tmp = tempfile.mkdtemp(prefix="launcher_probe_")
after = {}            # role -> backend still empty after the role returned


def run(name, fn):
    try:
        rc = fn()
    except Exception as e:          # a role may die once its children "exit"
        rc = f"{type(e).__name__}: {e}"
    after[name] = (xla_bridge._backends == {}, str(rc))


from multiverso_tpu.apps import fleet_main, word2vec_main

run("spawn_ranks", lambda: word2vec_main.main(
    ["-train_file=/nonexistent", "-world_size=2"]))
run("router", lambda: fleet_main.main(
    ["-fleet_role=router", "-serve_duration=0.2",
     f"-fleet_addr_file={tmp}/router"]))
run("drain", lambda: fleet_main.main(
    ["-fleet_role=drain", "-fleet_router=127.0.0.1:1"]))
run("local", lambda: fleet_main.main(
    ["-fleet_role=local", "-fleet_replicas=2", "-fleet_synthetic=64x8@1",
     "-serve_duration=0.2", f"-fleet_addr_file={tmp}/local"]))
run("ps_fleet", lambda: fleet_main.main(
    ["-fleet_role=ps_fleet", "-ps_fleet_shards=1", "-serve_duration=0.2",
     f"-ps_fleet_dir={tmp}/ps"]))

spec = importlib.util.spec_from_file_location(
    "serve_bench", os.path.join(sys.argv[1], "scripts", "serve_bench.py"))
serve_bench = importlib.util.module_from_spec(spec)
spec.loader.exec_module(serve_bench)
sys.argv = ["serve_bench.py", "--dry-run", "--replicas", "2",
            "--telemetry-dir", f"{tmp}/bench"]
run("serve_bench", serve_bench.main)

print("PROBE " + json.dumps({"spawns": spawns, "after": after}), flush=True)
os._exit(0)           # leaked listener threads must not hold the exit
"""


def test_no_launcher_role_holds_a_backend_when_it_spawns():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=_REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", _PROBE, _REPO], env=env,
                          capture_output=True, text=True, timeout=120)
    line = next((ln for ln in proc.stdout.splitlines()
                 if ln.startswith("PROBE ")), None)
    assert line, proc.stdout[-2000:] + proc.stderr[-4000:]
    seen = json.loads(line[len("PROBE "):])
    spawned = [role for role, _ in seen["spawns"]]
    # every launcher reached its spawn site...
    assert spawned.count("multiverso_tpu.apps.word2vec_main") == 2, spawned
    assert spawned.count("replica") >= 4, spawned     # local + serve_bench
    assert "multiverso_tpu.apps.ps_shard_main" in spawned, spawned
    assert "router" in spawned, spawned               # serve_bench's router
    # ...with no backend initialized, at any of them
    assert all(empty for _, empty in seen["spawns"]), seen["spawns"]
    # and the roles that start or talk to device holders never took one
    for role in ("spawn_ranks", "router", "drain", "local"):
        assert seen["after"][role][0], (role, seen["after"][role])
    assert seen["after"]["router"][1] == "0", seen["after"]["router"]


def test_sigterm_to_a_launcher_stops_its_children():
    """An orphaned child keeps its chip, so a launcher killed with SIGTERM
    must take its replicas with it (utils/chips.sigterm_as_interrupt)."""
    import signal
    import time

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=_REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    launcher = subprocess.Popen(
        [sys.executable, "-m", "multiverso_tpu.apps.fleet_main",
         "-fleet_role=local", "-fleet_replicas=1", "-fleet_synthetic=64x8@1",
         "-serve_buckets=8", "-serve_duration=120",
         "-telemetry_alerts=false", "-telemetry_flight=false"],
        env=env, cwd=_REPO, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    try:
        deadline = time.monotonic() + 60
        children = []
        while not children and time.monotonic() < deadline:
            assert launcher.poll() is None, "launcher exited during bring-up"
            out = subprocess.run(["pgrep", "-P", str(launcher.pid)],
                                 capture_output=True, text=True).stdout
            children = [int(p) for p in out.split()]
            time.sleep(0.1)
        assert children, "no replica was spawned"
        launcher.send_signal(signal.SIGTERM)
        launcher.wait(timeout=30)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and any(
                os.path.exists(f"/proc/{pid}") for pid in children):
            time.sleep(0.1)
        assert not any(os.path.exists(f"/proc/{pid}") for pid in children)
    finally:
        if launcher.poll() is None:
            launcher.kill()
        for pid in children:
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
