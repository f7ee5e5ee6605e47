#!/usr/bin/env python3
"""One open-loop generator process: ``python3 benchmark/loadgen.py <args.json>``.

It never touches a device (start it with ``JAX_PLATFORMS=cpu``: importing the
serving client imports jax). It draws its whole schedule and every request's
keys from the seed and prints ``loaded``; on ``warm`` from standard input it
connects one ``ServingClient``, sends one request of every bucket's size and
prints ``ready``; then it reads the window's start (a ``time.monotonic()``
value, one clock for every process of the host), sends each of its requests
when it is due whatever became of the earlier ones, waits ``straggler_s``
after the last, and writes due, sent and done times, each request's outcome
and the reply rows of the sampled requests to ``out``.
"""
from __future__ import annotations

import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(0, os.path.dirname(_HERE))

UNANSWERED, OK, SHED, BAD_SHAPE, SEND_ERROR = 0, 1, 2, 3, 4
SPIN_S = 0.0003


def main(argv=None) -> int:
    import numpy as np
    import traffic_gen
    with open((argv or sys.argv)[1]) as f:
        a = json.load(f)
    from multiverso_tpu.core.actor import MsgType
    from multiverso_tpu.parallel.net import unpack_serve_payload
    from multiverso_tpu.serving import ServingClient
    if a.get("sample_rate") is not None:
        from multiverso_tpu.utils.configure import set_flag
        set_flag("telemetry_sample_rate", float(a["sample_rate"]))

    due, sizes = traffic_gen.open_loop_schedule(
        a["seed"], a["rate"], a["seconds"], a["keys_lo"], a["keys_hi"])
    mine = np.arange(a["gen_index"], len(due), a["n_gens"])
    perm = traffic_gen.key_permutation(a["rows"])
    keys = [traffic_gen.zipf_keys(a["seed"], int(i), int(sizes[i]),
                                  a["zipf"], a["rows"], perm) for i in mine]
    sample = set(int(i) for i in a["sample"])
    n = len(mine)
    sent = np.full(n, np.nan)
    handed = np.full(n, np.nan)
    done = np.full(n, np.nan)
    status = np.zeros(n, np.int8)
    replies = {}

    print("loaded", flush=True)
    if sys.stdin.readline().strip() != "warm":
        return 1
    client = ServingClient(a["host"], a["port"])
    try:
        for size in a["warm_sizes"]:        # every bucket, outside the window
            client.lookup(np.zeros(size, np.int32), deadline_ms=60_000,
                          timeout=120)

        def on_done(res, k):
            done[k] = time.monotonic()
            if not res.slot:
                return
            msg = res.slot[0]
            if msg.type == MsgType.Reply_Error:
                status[k] = SHED
                return
            values = unpack_serve_payload(msg.data[1:])
            if values.shape[0] != len(keys[k]):
                status[k] = BAD_SHAPE
                return
            status[k] = OK
            if int(mine[k]) in sample:
                replies[int(mine[k])] = np.array(values)
            res.slot.clear()                # let the payload go

        print("ready", flush=True)
        t0 = float(sys.stdin.readline())
        for k in range(n):
            target = t0 + due[mine[k]]
            while True:
                left = target - time.monotonic()
                if left <= 0:
                    break
                if left > SPIN_S:
                    time.sleep(left - SPIN_S)
            sent[k] = time.monotonic()
            try:
                client.request_async(
                    keys[k], deadline_ms=a["deadline_ms"],
                    on_done=lambda res, k=k: on_done(res, k))
            except OSError:
                status[k] = SEND_ERROR
                done[k] = time.monotonic()
            handed[k] = time.monotonic()
        end = t0 + a["seconds"] + a["straggler_s"]
        while time.monotonic() < end and np.isnan(done).any():
            time.sleep(0.005)
    finally:
        client.close()
    out = {"index": mine, "due": due[mine], "sent": sent - t0,
           "handed": handed - t0, "done": done - t0, "status": status,
           "size": sizes[mine]}
    out.update({f"reply_{i}": v for i, v in replies.items()})
    np.savez(a["out"], **out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
