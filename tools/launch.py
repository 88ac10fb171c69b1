#!/usr/bin/env python
"""Distributed job launcher (ref: tools/launch.py -> dmlc tracker).

TPU-native: instead of scheduler/server/worker roles over ZMQ, every process
is a JAX distributed client (jax.distributed.initialize) and gradients ride
DCN/ICI collectives. Supports local multi-process launch (the reference's
`--launcher local` used by the nightly dist tests) and ssh host lists.
"""
import argparse
import glob
import os
import secrets
import subprocess
import sys


# PCI device ids of Google (vendor 0x1ae0) TPU chips, as jax's own
# hardware_utils lists them: v3, v4, v5p, v5e, v6e, 7x and one unnamed part
_TPU_PCI_DEVICE_IDS = {"0x0027", "0x0056", "0x005e", "0x0062", "0x0063",
                       "0x006f", "0x0076"}


def _local_tpu_chips():
    """TPU chips on this host's PCI bus, counted from sysfs: the launcher
    must not import jax, or it would hold the chips its ranks ask for."""
    n = 0
    for vendor in glob.glob("/sys/bus/pci/devices/*/vendor"):
        try:
            with open(vendor) as f:
                if f.read().strip() != "0x1ae0":
                    continue
            with open(os.path.join(os.path.dirname(vendor), "device")) as f:
                n += f.read().strip() in _TPU_PCI_DEVICE_IDS
        except OSError:
            continue
    return n


def main():
    p = argparse.ArgumentParser()
    p.add_argument("-n", "--num-workers", type=int, required=True)
    p.add_argument("--launcher", default="local", choices=["local", "ssh"])
    p.add_argument("-H", "--hostfile", default=None)
    p.add_argument("--coordinator", default="127.0.0.1:12345")
    p.add_argument("command", nargs=argparse.REMAINDER)
    args = p.parse_args()
    cmd = args.command
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    assert cmd, "no command given"
    # one job secret for the whole gang: authenticates the PS optimizer
    # blob (the only pickle on the PS wire)
    ps_secret = os.environ.get("MXTPU_PS_SECRET") or secrets.token_hex(16)

    if args.launcher == "local":
        chips = _local_tpu_chips()
        if (chips and args.num_workers > 1
                and os.environ.get("JAX_PLATFORMS", "") != "cpu"):
            # a TPU chip belongs to one process: N local ranks would each
            # open every chip, and all but the first fail or hang
            sys.exit(
                f"launch.py: this host has {chips} TPU chip(s); "
                f"{args.num_workers} local ranks would each open all of "
                f"them, and a chip belongs to one process. Drive the "
                f"chips of one host from ONE process (a jax.sharding.Mesh "
                f"over jax.devices()), or set JAX_PLATFORMS=cpu for a "
                f"CPU-only run.")
        procs = []
        for rank in range(args.num_workers):
            env = dict(os.environ)
            env.update({
                "MXTPU_COORDINATOR": args.coordinator,
                "MXTPU_NUM_PROCESSES": str(args.num_workers),
                "MXTPU_PROCESS_ID": str(rank),
                "MXTPU_PS_SECRET": ps_secret,
                # reference-compatible names (ref: DMLC_ROLE env protocol)
                "DMLC_ROLE": "worker",
                "DMLC_NUM_WORKER": str(args.num_workers),
                "DMLC_WORKER_ID": str(rank),
            })
            procs.append(subprocess.Popen(cmd, env=env))
        rc = 0
        for proc in procs:
            rc |= proc.wait()
        sys.exit(rc)
    else:
        hosts = [h.strip() for h in open(args.hostfile) if h.strip()]
        procs = []
        for rank in range(args.num_workers):
            host = hosts[rank % len(hosts)]
            remote_env = (
                f"MXTPU_COORDINATOR={args.coordinator} "
                f"MXTPU_NUM_PROCESSES={args.num_workers} "
                f"MXTPU_PROCESS_ID={rank}"
            )
            # the job secret rides the first stdin line, NOT the command
            # line (remote /proc/<pid>/cmdline is world-readable); the
            # explicit `sh -c` keeps this independent of the remote login
            # shell.  Launched commands do not receive the parent's stdin
            # (training jobs are non-interactive).
            remote_cmd = ("exec /bin/sh -c 'IFS= read -r MXTPU_PS_SECRET "
                          "&& export MXTPU_PS_SECRET && exec env " +
                          remote_env + " " + " ".join(cmd) + "'")
            p = subprocess.Popen(["ssh", host, remote_cmd],
                                 stdin=subprocess.PIPE, text=True)
            p.stdin.write(ps_secret + "\n")
            p.stdin.close()
            procs.append(p)
        rc = 0
        for proc in procs:
            rc |= proc.wait()
        sys.exit(rc)


if __name__ == "__main__":
    main()
