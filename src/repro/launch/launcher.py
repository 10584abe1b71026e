"""Launching utilities (paper §6.6): build experiment variants and
stack/queue them over fixed local resources.

The paper's example: an 8-GPU/40-CPU box running 30 variants 2-GPUs-each,
4 at a time.  On a TPU host a chip belongs to one process at a time, and a
JAX process claims every chip of its host, so accelerator jobs run one at a
time; CPU-only jobs (``JAX_PLATFORMS=cpu``) may run ``capacity`` at once.
The launcher starts the next job as a slot frees, recording results in a
per-variant directory tree that mirrors the variant spec (paper: "results
are recorded into a file structure which matches that of the variants
generated").

Multi-pod: ``emit_pod_script`` writes the per-pod launch script that sets
jax.distributed coordinator/process_id — the real-cluster path (cannot be
executed in this container; the dry-run validates the mesh instead).
"""
from __future__ import annotations

import itertools
import json
import os
import shlex
import subprocess
import sys
import time
from typing import Dict, List, Sequence


def make_variants(base: Dict, **grids) -> List[Dict]:
    """Cartesian product of grid values over a base config dict."""
    keys = list(grids)
    out = []
    for combo in itertools.product(*(grids[k] for k in keys)):
        v = dict(base)
        v.update(dict(zip(keys, combo)))
        out.append(v)
    return out


def variant_name(variant: Dict, keys: Sequence[str]) -> str:
    return "_".join(f"{k}-{variant[k]}" for k in keys)


def launch_queue(commands: List[List[str]], *, capacity: int = 1,
                 log_dir: str = "runs", env_extra: Dict = None,
                 poll_s: float = 0.5) -> List[int]:
    """Run commands with at most ``capacity`` concurrent; returns exit codes.

    Each command i logs to {log_dir}/job_{i:03d}.log.  Slots are freed as
    jobs finish and the next queued job starts in its place (paper §6.6).
    ``capacity > 1`` requires CPU-only jobs: two JAX processes must never
    contend for one chip.
    """
    base_env = dict(os.environ)
    base_env.update(env_extra or {})
    if capacity > 1 and base_env.get("JAX_PLATFORMS") != "cpu":
        raise ValueError(
            f"capacity={capacity}: concurrent jobs would share the host's "
            f"chips; run one job at a time or set JAX_PLATFORMS=cpu")
    os.makedirs(log_dir, exist_ok=True)
    running: Dict[int, subprocess.Popen] = {}
    codes = [None] * len(commands)
    nxt = 0
    files = {}
    while nxt < len(commands) or running:
        while nxt < len(commands) and len(running) < capacity:
            log = open(os.path.join(log_dir, f"job_{nxt:03d}.log"), "w")
            env = dict(base_env, JOB_INDEX=str(nxt))
            p = subprocess.Popen(commands[nxt], stdout=log, stderr=log, env=env)
            running[nxt] = p
            files[nxt] = log
            nxt += 1
        done = [i for i, p in running.items() if p.poll() is not None]
        for i in done:
            codes[i] = running[i].returncode
            files[i].close()
            del running[i], files[i]
        if running:
            time.sleep(poll_s)
    return codes


def run_variants(script: str, variants: List[Dict], vary_keys: Sequence[str],
                 *, capacity: int = 1, out_root: str = "runs",
                 python: str = sys.executable) -> List[int]:
    """Launch {python} -m {script} --key value ... per variant, queued."""
    cmds, names = [], []
    for v in variants:
        name = variant_name(v, vary_keys)
        vdir = os.path.join(out_root, name)
        os.makedirs(vdir, exist_ok=True)
        with open(os.path.join(vdir, "variant.json"), "w") as f:
            json.dump(v, f, indent=1)
        cmd = [python, "-m", script]
        for k, val in v.items():
            if isinstance(val, bool):
                if val:
                    cmd.append(f"--{k.replace('_', '-')}")
            else:
                cmd += [f"--{k.replace('_', '-')}", str(val)]
        cmd += ["--log-dir", vdir]
        cmds.append(cmd)
        names.append(name)
    print(f"queueing {len(cmds)} variants, capacity {capacity}:")
    for n in names:
        print("  ", n)
    return launch_queue(cmds, capacity=capacity, log_dir=out_root)


POD_SCRIPT = """#!/bin/bash
# Auto-generated per-pod launch script ({n_pods} pods x 256 chips).
# Pod index comes from the cluster scheduler; coordinator is pod 0.
set -e
export POD_INDEX=${{POD_INDEX:?set by scheduler}}
export COORDINATOR={coordinator}
python -c "
import jax
jax.distributed.initialize(
    coordinator_address='$COORDINATOR',
    num_processes={n_pods},
    process_id=int('$POD_INDEX'))
from repro.launch import train
train.main({train_args!r})
"
"""


def emit_pod_script(path: str, *, n_pods: int = 2,
                    coordinator: str = "pod0:8476",
                    train_args: List[str] = ()):
    with open(path, "w") as f:
        f.write(POD_SCRIPT.format(n_pods=n_pods, coordinator=coordinator,
                                  train_args=list(train_args)))
    os.chmod(path, 0o755)
    return path
