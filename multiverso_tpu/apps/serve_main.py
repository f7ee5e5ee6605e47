"""Serving CLI: stand up a dynamic-batching inference service.

The deployment shape is the checkpoint-to-replica handoff
(docs/SERVING.md): training emits checkpoints, this process follows the
checkpoint directory with a frozen read-only replica and serves batched
row lookups over the DCN framing — no coordination channel with the
trainer beyond the filesystem.

    python -m multiverso_tpu.apps.serve_main \\
        -checkpoint_dir=/ckpts -serve_table=matrix_0 \\
        -serve_port=7070 -serve_buckets=8,16,32,64 -serve_max_wait_ms=2

Flags (full list in README's CLI table): ``-serve_port``,
``-serve_buckets``, ``-serve_max_wait_ms``, ``-serve_max_batch``,
``-serve_admission``, ``-serve_wire_dtype``, ``-serve_addr_file``,
``-serve_duration``. ``-telemetry_dir`` exports the ``serve.*`` metric
family like any other app.
"""

from __future__ import annotations

import sys
import time
from typing import List

from multiverso_tpu.apps._runner import run_app, serve_config
from multiverso_tpu.utils.configure import (define_double, define_string,
                                            get_flag)
from multiverso_tpu.utils.log import check, log

define_string("checkpoint_dir", "", "checkpoint directory to serve from "
              "(latest complete ckpt_* is loaded and followed)")
define_string("serve_table", "", "table name to serve rows from (empty = "
              "the checkpoint's first table)")
define_double("serve_refresh_s", 5.0, "seconds between checkpoint "
              "refresh polls (hot-swap cadence)")


def _body(remaining: List[str]) -> int:
    del remaining
    from multiverso_tpu.serving import (CheckpointReplica,
                                        ReplicaLookupRunner, ServingService,
                                        cache_from_flags)

    ckpt_dir = str(get_flag("checkpoint_dir"))
    check(bool(ckpt_dir), "-checkpoint_dir is required")
    cfg = serve_config()
    replica = CheckpointReplica(ckpt_dir)
    snap = replica.snapshot()
    table = str(get_flag("serve_table")) or snap.names[0]
    check(table in snap.names,
          f"-serve_table={table!r} not in checkpoint (has {snap.names})")
    replica.start_auto_refresh(float(get_flag("serve_refresh_s")))

    service = ServingService(host=cfg["host"], port=cfg["port"])
    service.register_runner(ReplicaLookupRunner(replica, table,
                                                cache=cache_from_flags()),
                            buckets=cfg["buckets"],
                            max_batch=cfg["max_batch"],
                            max_wait_ms=cfg["max_wait_ms"],
                            max_queue=cfg["max_queue"],
                            pipeline_depth=cfg["pipeline_depth"],
                            continuous=cfg["continuous"],
                            paged=cfg["paged"], kv_dtype=cfg["kv_dtype"],
                            kv_page=cfg["kv_page"],
                            kv_pages=cfg["kv_pages"],
                            prefix_entries=cfg["prefix_entries"])
    host, port = service.address
    log.info("serving table '%s' (step %d) at %s:%d", table, snap.step,
             host, port)
    addr_file = str(get_flag("serve_addr_file"))
    if addr_file:
        with open(addr_file + ".tmp", "w") as f:
            f.write(f"{host}:{port}")
        import os
        os.replace(addr_file + ".tmp", addr_file)

    duration = float(get_flag("serve_duration"))
    deadline = time.monotonic() + duration if duration > 0 else None
    try:
        while deadline is None or time.monotonic() < deadline:
            # Constant cadence on purpose: parks the main thread while
            # the service threads serve; 0.2s bounds Ctrl-C latency.
            time.sleep(0.2)  # graftlint: disable=poll-loop-no-backoff
    except KeyboardInterrupt:
        log.info("serve_main: interrupted, shutting down")
    finally:
        service.close()
        replica.close()
    return 0


def main(argv=None) -> int:
    # See fleet_main: serving processes convoy on the default 5ms GIL
    # switch interval; 0.5ms keeps request latency off that floor.
    sys.setswitchinterval(5e-4)
    return run_app(_body, list(argv if argv is not None else sys.argv[1:]))


if __name__ == "__main__":
    sys.exit(main())
