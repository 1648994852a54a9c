"""Multi-host bootstrap: coordinator/worker launch for jax.distributed.

The real-cluster shape (DESIGN.md §15): one process per host joins the
group via :func:`repro.launch.mesh.initialize_distributed` (env-var or
CLI addressing), builds the process-major ``(pod, data, model)`` mesh,
and trains with per-host sharded data.  The SAME entry point is the CI
harness — ``--local-procs N`` forks N workers on this machine, each a
separate jax process with its own ``XLA_FLAGS``-forced device count, so
a laptop or a CI runner exercises genuine cross-process collectives
(gloo) without a pod.

Driver (spawns workers, validates their reports)::

    PYTHONPATH=src python -m repro.launch.multihost \\
        --local-procs 4 --task smoke --metrics-dir /tmp/mh

Worker (what the driver execs; on a real cluster, run one per host with
REPRO_COORDINATOR/REPRO_NUM_PROCS/REPRO_PROC_ID exported, or pass
``--coordinator host:port --num-procs N --proc-id I``)::

    PYTHONPATH=src python -m repro.launch.multihost --worker --task smoke

Tasks:

* ``smoke``    — short real training run (reduced arch, Trainer.fit) with
  ``--sync-mode``; per-process metrics land in ``proc<i>.jsonl``.
* ``parity``   — the eventual-vs-sequential gate: both modes trained on
  identical data; final params must be bit-identical at staleness 0, and
  every process must report the same losses.
* ``elastic``  — checkpoint under one process count, restore + continue
  under another (the driver runs the two groups back to back).
* ``shard_check`` — every process reports its RecordIO shard assignment
  and stream checksums; the driver proves shards are disjoint, cover the
  epoch, and concatenate to the single-host stream.
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time
import zlib
from pathlib import Path

TASKS = ("smoke", "parity", "elastic", "shard_check")


# ---------------------------------------------------------------------------
# worker side

def _result_path(metrics_dir: str) -> Path:
    import jax
    return Path(metrics_dir) / f"proc{jax.process_index()}.jsonl"


def _report(metrics_dir: str, record: dict):
    p = _result_path(metrics_dir)
    p.parent.mkdir(parents=True, exist_ok=True)
    with open(p, "a") as f:
        f.write(json.dumps(record) + "\n")


def _tree_crc(tree) -> int:
    """Order-stable crc32 over every leaf's bytes (replicated trees give
    the same value on every process — the cross-host parity probe)."""
    import jax
    import numpy as np
    crc = 0
    for leaf in jax.tree.leaves(tree):
        crc = zlib.crc32(np.ascontiguousarray(np.asarray(leaf)).tobytes(),
                         crc)
    return crc


def _smoke_cfg(vocab: int = 32):
    from repro.configs import get_config
    from repro.models import reduced
    return reduced(get_config("qwen1.5-0.5b"), vocab=vocab, n_layers=2,
                   d_model=64, d_ff=128)


def _train(mesh, *, sync_mode: str, max_staleness: int, steps: int,
           batch: int, seed: int = 0, state=None, start_step: int = 0):
    """One short Trainer.fit over the per-host shard of the synthetic
    stream; returns (trainer, params, history)."""
    import jax
    from repro.data import PrefetchIterator, SyntheticLM
    from repro.train import TrainConfig, Trainer
    cfg = _smoke_cfg()
    tcfg = TrainConfig(lr=1e-2, total_steps=steps, log_every=max(steps, 1),
                       warmup_steps=1, sync_mode=sync_mode,
                       max_staleness=max_staleness, bucket_mb=0.001)
    data = SyntheticLM(cfg.vocab, 16, batch, seed=7, n_batches=steps,
                       process_index=jax.process_index(),
                       process_count=jax.process_count())
    it = iter(PrefetchIterator(data, depth=2))
    for _ in range(start_step):
        next(it, None)
    with jax.set_mesh(mesh):
        tr = Trainer(cfg, tcfg)
        params, opt = tr.fit(it, seed=seed, state=state,
                             start_step=start_step)
    return tr, params, tr.history


def _task_smoke(args, mesh):
    import jax
    tr, params, hist = _train(mesh, sync_mode=args.sync_mode,
                              max_staleness=args.max_staleness,
                              steps=args.steps, batch=args.batch)
    stale = (tr._ev.max_observed_staleness if tr._ev is not None else 0)
    _report(args.metrics_dir, {
        "task": "smoke", "proc": jax.process_index(),
        "sync_mode": args.sync_mode, "max_staleness": args.max_staleness,
        "observed_staleness": stale,
        "losses": [h["loss"] for h in hist],
        "params_crc": _tree_crc(params)})
    assert stale <= args.max_staleness, (stale, args.max_staleness)


def _task_parity(args, mesh):
    """Eventual at staleness 0 vs sequential: bit-identical params."""
    import jax
    import numpy as np
    _, p_seq, h_seq = _train(mesh, sync_mode="sequential", max_staleness=0,
                             steps=args.steps, batch=args.batch)
    _, p_ev, h_ev = _train(mesh, sync_mode="eventual", max_staleness=0,
                           steps=args.steps, batch=args.batch)
    for a, b in zip(jax.tree.leaves(p_seq), jax.tree.leaves(p_ev)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [h["loss"] for h in h_seq] == [h["loss"] for h in h_ev]
    _report(args.metrics_dir, {
        "task": "parity", "proc": jax.process_index(),
        "losses": [h["loss"] for h in h_seq],
        "params_crc": _tree_crc(p_seq), "bit_exact": True})


def _task_elastic(args, mesh):
    """Phase is selected by --elastic-phase: 'save' trains then commits a
    checkpoint (process 0 writes; params are replicated); 'restore' —
    typically under a DIFFERENT process count — loads it, proves cross-
    process parity, and continues training."""
    import jax
    from repro.train.checkpoint import load_checkpoint, save_checkpoint
    ckpt = str(Path(args.metrics_dir) / "elastic_ckpt")
    if args.elastic_phase == "save":
        _, params, hist = _train(mesh, sync_mode=args.sync_mode,
                                 max_staleness=args.max_staleness,
                                 steps=args.steps, batch=args.batch)
        if jax.process_index() == 0:
            save_checkpoint(ckpt, {"params": params}, step=args.steps - 1)
        _report(args.metrics_dir, {
            "task": "elastic_save", "proc": jax.process_index(),
            "procs": jax.process_count(), "params_crc": _tree_crc(params),
            "losses": [h["loss"] for h in hist]})
        return
    # restore under this (different) process count
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    restored, step = load_checkpoint(ckpt)
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(lambda x: jax.device_put(x, rep),
                          restored["params"])
    crc = _tree_crc(params)
    with jax.set_mesh(mesh):
        from repro.train import TrainConfig, Trainer
        tr = Trainer(_smoke_cfg(), TrainConfig(
            lr=1e-2, total_steps=step + 1 + args.steps,
            log_every=1, warmup_steps=1, sync_mode=args.sync_mode,
            max_staleness=args.max_staleness, bucket_mb=0.001))
        opt = tr.optimizer.init(params)
        from repro.data import SyntheticLM
        data = SyntheticLM(_smoke_cfg().vocab, 16, args.batch, seed=7,
                           n_batches=step + 1 + args.steps,
                           process_index=jax.process_index(),
                           process_count=jax.process_count())
        it = iter(data)
        for _ in range(step + 1):
            next(it, None)
        params2, _ = tr.fit(it, state=(params, opt), start_step=step + 1)
    _report(args.metrics_dir, {
        "task": "elastic_restore", "proc": jax.process_index(),
        "procs": jax.process_count(), "restored_step": step,
        "restored_crc": crc, "continued_crc": _tree_crc(params2),
        "losses": [h["loss"] for h in tr.history]})


def _task_shard_check(args, mesh):
    """Per-host RecordIO shard assignment: report this host's record
    indices and stream checksum; assert the local stream equals the
    matching row-slice of a single-host iterator."""
    import jax
    import numpy as np
    from repro.data import DataIterator, RecordReader
    from repro.data.pipeline import global_batch_slice
    path = str(Path(args.metrics_dir) / "shards.rec")  # driver pre-writes
    decode = lambda b: np.frombuffer(b, np.int32)
    pi, pc = jax.process_index(), jax.process_count()
    it = DataIterator(RecordReader(path), batch=args.batch,
                      decode_fn=decode, seed=3, process_index=pi,
                      process_count=pc)
    ref = DataIterator(RecordReader(path), batch=args.batch,
                       decode_fn=decode, seed=3)
    lo, hi = global_batch_slice(args.batch, pi, pc)
    crc = 0
    n_local = 0
    for mine, full in zip(it, ref):
        np.testing.assert_array_equal(mine, full[lo:hi])
        crc = zlib.crc32(np.ascontiguousarray(mine).tobytes(), crc)
        n_local += mine.shape[0]
    _report(args.metrics_dir, {
        "task": "shard_check", "proc": pi, "procs": pc,
        "record_indices": [int(i) for i in it.record_indices()],
        "n_local": n_local, "stream_crc": crc})


def run_worker(args) -> int:
    # join the group BEFORE any other jax device use; addressing via CLI
    # flags if given, else the REPRO_* env the driver exported
    from repro.launch.mesh import (initialize_distributed,
                                   make_distributed_mesh)
    initialize_distributed(args.coordinator, args.num_procs, args.proc_id)
    import jax
    mesh = make_distributed_mesh()
    task_fn = {"smoke": _task_smoke, "parity": _task_parity,
               "elastic": _task_elastic,
               "shard_check": _task_shard_check}[args.task]
    task_fn(args, mesh)
    # per-process metrics registry -> the proc JSONL (the CI artifact)
    from repro import obs
    obs.get_metrics().dump_jsonl(str(_result_path(args.metrics_dir)))
    print(f"[proc {jax.process_index()}] task {args.task} OK", flush=True)
    return 0


# ---------------------------------------------------------------------------
# driver side

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_group(args, n_procs: int, extra: list[str]) -> None:
    """Fork n_procs workers (one jax process each), stream their output,
    fail loudly on any nonzero exit or on timeout."""
    port = _free_port()
    procs = []
    for i in range(n_procs):
        env = dict(os.environ)
        env.update(
            REPRO_COORDINATOR=f"127.0.0.1:{port}",
            REPRO_NUM_PROCS=str(n_procs), REPRO_PROC_ID=str(i),
            JAX_PLATFORMS="cpu",        # virtual CPU devices, never a chip
            XLA_FLAGS="--xla_force_host_platform_device_count="
                      f"{args.local_devices}")
        cmd = [sys.executable, "-m", "repro.launch.multihost", "--worker",
               "--task", args.task, "--metrics-dir", args.metrics_dir,
               "--steps", str(args.steps), "--batch", str(args.batch),
               "--sync-mode", args.sync_mode,
               "--max-staleness", str(args.max_staleness), *extra]
        procs.append(subprocess.Popen(cmd, env=env))
    deadline = time.time() + args.timeout
    failed = []
    for i, p in enumerate(procs):
        try:
            rc = p.wait(timeout=max(deadline - time.time(), 1))
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise SystemExit(f"worker {i} timed out after {args.timeout}s")
        if rc != 0:
            failed.append((i, rc))
    if failed:
        raise SystemExit(f"workers failed: {failed}")


def _load_reports(metrics_dir: str, task: str) -> list[dict]:
    out = []
    for p in sorted(Path(metrics_dir).glob("proc*.jsonl")):
        for line in p.read_text().splitlines():
            rec = json.loads(line)
            if rec.get("task", "").startswith(task):
                out.append(rec)
    return out


def _check_parity(reports: list[dict]):
    crcs = {r["params_crc"] for r in reports}
    losses = {tuple(r["losses"]) for r in reports}
    if len(crcs) != 1 or len(losses) != 1:
        raise SystemExit(f"cross-process divergence: crcs={crcs} "
                         f"losses={losses}")


def _check_shards(reports: list[dict], n_records: int, batch: int):
    all_idx: list[int] = []
    for r in reports:
        all_idx.extend(r["record_indices"])
    if len(all_idx) != len(set(all_idx)):
        raise SystemExit("per-host shards overlap")
    n_full = (n_records // batch) * batch
    if len(set(all_idx)) != n_full:
        raise SystemExit(f"shards cover {len(set(all_idx))} records, "
                         f"expected the full epoch {n_full}")


def run_driver(args) -> int:
    Path(args.metrics_dir).mkdir(parents=True, exist_ok=True)
    for old in Path(args.metrics_dir).glob("proc*.jsonl"):
        old.unlink()
    if args.task == "shard_check":
        import numpy as np
        from repro.data import pack_records
        rng = np.random.default_rng(0)
        payloads = [rng.integers(0, 1000, 8, dtype=np.int32).tobytes()
                    for _ in range(args.n_records)]
        pack_records(str(Path(args.metrics_dir) / "shards.rec"), payloads)
    if args.task == "elastic":
        # checkpoint under N procs, restore + continue under M != N
        _spawn_group(args, args.local_procs, ["--elastic-phase", "save"])
        restore = args.restore_procs or (4 if args.local_procs == 2
                                         else max(args.local_procs // 2, 1))
        _spawn_group(args, restore, ["--elastic-phase", "restore"])
        saves = _load_reports(args.metrics_dir, "elastic_save")
        rests = _load_reports(args.metrics_dir, "elastic_restore")
        _check_parity([{**r, "losses": []} for r in saves])
        save_crc = saves[0]["params_crc"]
        for r in rests:
            if r["restored_crc"] != save_crc:
                raise SystemExit(
                    f"elastic restore diverged: saved crc {save_crc}, "
                    f"proc {r['proc']} restored {r['restored_crc']}")
        _check_parity([{"params_crc": r["continued_crc"],
                        "losses": r["losses"]} for r in rests])
        print(f"elastic OK: saved@{args.local_procs} procs, "
              f"restored+continued@{restore} procs, crc {save_crc}")
        return 0
    _spawn_group(args, args.local_procs, [])
    reports = _load_reports(args.metrics_dir, args.task)
    if len(reports) != args.local_procs:
        raise SystemExit(f"expected {args.local_procs} reports, "
                         f"got {len(reports)}")
    if args.task == "smoke" and args.max_staleness > 0:
        # bounded-staleness smoke: per-pod params legitimately diverge
        # (each pod integrates its own local+stored-remote gradient view
        # while a bucket is stale), so the gate is the staleness bound +
        # finite losses, not cross-process crc equality
        import math
        for r in reports:
            if r["observed_staleness"] > args.max_staleness:
                raise SystemExit(f"proc {r['proc']} staleness "
                                 f"{r['observed_staleness']} > bound "
                                 f"{args.max_staleness}")
            if not all(math.isfinite(x) for x in r["losses"]):
                raise SystemExit(f"proc {r['proc']} non-finite losses: "
                                 f"{r['losses']}")
        print(f"smoke OK across {args.local_procs} procs: staleness "
              f"<= {args.max_staleness}, crcs "
              f"{sorted({r['params_crc'] for r in reports})}")
    elif args.task in ("smoke", "parity"):
        _check_parity(reports)
        print(f"{args.task} OK across {args.local_procs} procs: "
              f"losses {reports[0]['losses']}")
    else:  # shard_check
        _check_shards(reports, args.n_records, args.batch)
        print(f"shard_check OK: {args.local_procs} disjoint shards cover "
              f"the epoch")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--worker", action="store_true",
                    help="run as ONE process of the group (driver-internal "
                         "on CI; on a real cluster, one per host)")
    ap.add_argument("--task", choices=TASKS, default="smoke")
    ap.add_argument("--local-procs", type=int, default=2,
                    help="driver: number of worker processes to fork")
    ap.add_argument("--local-devices", type=int, default=2,
                    help="devices per worker process (XLA forced host "
                         "platform count)")
    ap.add_argument("--restore-procs", type=int, default=0,
                    help="elastic: process count for the restore phase "
                         "(default: 4 when saving at 2, else N/2)")
    ap.add_argument("--metrics-dir", default="multihost-report",
                    help="per-process JSONL reports + artifacts")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--batch", type=int, default=8,
                    help="GLOBAL batch (split over processes)")
    ap.add_argument("--n-records", type=int, default=64,
                    help="shard_check: RecordIO file size")
    ap.add_argument("--sync-mode",
                    choices=["auto", "sequential", "eventual"],
                    default="sequential")
    ap.add_argument("--max-staleness", type=int, default=0)
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="driver: per-group wall-clock budget (s)")
    ap.add_argument("--elastic-phase", choices=["save", "restore"],
                    default="save")
    # worker-side CLI addressing (overrides the REPRO_* env)
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT")
    ap.add_argument("--num-procs", type=int, default=None)
    ap.add_argument("--proc-id", type=int, default=None)
    args = ap.parse_args(argv)
    if args.worker:
        return run_worker(args)
    return run_driver(args)


if __name__ == "__main__":
    raise SystemExit(main())
