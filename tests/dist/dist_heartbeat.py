#!/usr/bin/env python
"""TCP heartbeat dead-node detection across real processes (ref: ps-lite
Heartbeat/GetDeadNodes; reference surfaced as KVStore::get_num_dead_node).

Launched with W>=3 workers.  The LAST rank exits immediately after its
first beat; the survivors must observe exactly one dead node once the
timeout lapses, and zero dead nodes before that.  Runs
on raw sockets — no jax.distributed — so a worker vanishing cannot wedge a
collective."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

os.environ["MXTPU_HEARTBEAT_INTERVAL"] = "0.3"
os.environ["MXTPU_HEARTBEAT_TIMEOUT"] = "2.0"

from incubator_mxnet_tpu import config as _config
from incubator_mxnet_tpu.kvstore import _TcpHeartbeat


def main():
    rank = int(os.environ["MXTPU_PROCESS_ID"])
    nw = int(os.environ["MXTPU_NUM_PROCESSES"])
    assert nw >= 3, "run with -n >= 3"
    host, port = _config.get("MXTPU_COORDINATOR").rsplit(":", 1)
    hb = _TcpHeartbeat.get(rank, nw, host, int(port) + 29,
                           _config.get("MXTPU_HEARTBEAT_INTERVAL"),
                           _config.get("MXTPU_HEARTBEAT_TIMEOUT"))

    if rank == nw - 1:
        # doomed worker: beat once (already done in __init__), then vanish
        print(f"rank {rank}/{nw}: dist_heartbeat OK (exiting early)")
        sys.stdout.flush()
        os._exit(0)

    # while everyone alive and beating: no dead nodes
    time.sleep(1.0)
    assert hb.num_dead() == 0, hb.num_dead()

    # after the doomed worker's beat goes stale: exactly one dead node
    deadline = time.time() + 15
    while time.time() < deadline:
        if hb.num_dead() == 1:
            break
        time.sleep(0.3)
    assert hb.num_dead() == 1, hb.num_dead()
    print(f"rank {rank}/{nw}: dist_heartbeat OK")
    sys.stdout.flush()
    if rank == 0:
        # exit barrier: rank 0 hosts the heartbeat service, and a survivor
        # that has not made its observation yet when the service goes away
        # spends minutes in reconnect retries. Stay until every other
        # survivor has left too (its beats go stale).
        deadline = time.time() + 30
        while time.time() < deadline and hb.num_dead() < nw - 1:
            time.sleep(0.3)


if __name__ == "__main__":
    main()
