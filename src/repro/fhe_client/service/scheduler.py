"""Dual-stream scheduler: the RSC mode policy executed on device groups.

``core.scheduler`` reproduces the paper's dual-RSC task scheduling
analytically; this module *executes* that policy. Each stream is one
device group (``distributed.sharding.stream_groups``) standing in for one
Reconfigurable Streaming Core; jobs from the coalescing batcher are
assigned to streams round by round with the SAME pure policy functions
(``assign_streams``/``round_mode``) the analytic model exposes, so the
dispatch log the service records is — by construction, and by test — the
schedule ``core.scheduler.plan_rounds`` predicts.

Execution:

  * single-device stream — the client's existing jitted cores, operands
    committed to the stream's device (two 1-device streams = the 2xENC /
    2xDEC / ENC+DEC modes running concurrently via async dispatch, one
    jit trace shared by both streams);
  * multi-device stream — the client's untraced core impls shard_map'ed
    over the group's 1-D 'batch' mesh (the batch axis of the limb-folded
    grid splits across devices; per-shard nonce offsets keep row r of a
    batch on ``nonce0 + r``, bit-identical to the unsharded launch).

All launches in a round go out before anything blocks — jax's async
dispatch keeps every stream's device queue busy, which is the whole point
of the dual-stream layout under the paper's 10:1 encrypt-heavy mix.
"""

from __future__ import annotations

import dataclasses
from collections import deque

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import scheduler as policy
from repro.distributed import sharding as shd
from repro.fhe_client.service.batcher import DecJob, EncJob, now
from repro.fhe_client.service.faults import (
    AllStreamsFailed, EventLog, is_stream_fault,
)
from repro.kernels import ops as kops
from repro.telemetry import close_annotation


@jax.jit
def _unstack(x):
    """One program: a bucket's (B, ...) array -> B arrays of one row."""
    return tuple(x[i] for i in range(x.shape[0]))


def bucket_rows(out, n_real: int) -> list:
    """The ``n_real`` real rows of a launched encrypt output, each a device
    array of its own, made by one program per device that holds a shard,
    with each row's copy to the host started: the copies run as soon as
    the kernel ends, and each row comes to the host as a buffer of its
    own (a whole-bucket transfer measured about 4x slower on a TPU
    v5e)."""
    shards = sorted(out.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    rows = [r for s in shards for r in _unstack(s.data)][:n_real]
    for r in rows:
        r.copy_to_host_async()
    return rows


@dataclasses.dataclass(frozen=True)
class DispatchRecord:
    """One job launch: which stream ran what, under which top-level mode.
    ``attempt > 0`` marks a retry of a failed stream's job (same job, same
    nonce lease, surviving stream)."""
    round: int
    stream: int
    kind: str                       # 'enc' | 'dec'
    mode: policy.Mode
    bucket: int
    rids: tuple
    attempt: int = 0
    t_launch: float = 0.0           # monotonic launch timestamp (0 = unset)


class StreamExecutor:
    """One execution stream (device group) running the client cores.

    Multi-tenant: ``client_for`` maps a job's lane key to that tenant's
    client; a lane of None is the anonymous default ``client``. Sharded
    cores are cached ON the tenant's client object (keyed by this
    stream's device ids), NOT in an executor-side table keyed by
    ``id(client)`` — an id-keyed table would recreate exactly the
    GC/id-reuse staleness this PR fixes, and an executor-held strong
    reference would keep evicted tenants' compiled cores (and key
    material) alive past registry eviction. Cores die with the client.
    """

    def __init__(self, client, devices, index: int, client_for=None):
        self.client = client
        self._client_for = client_for
        self.devices = tuple(devices)
        self.index = index
        self.n_shards = len(self.devices)
        if self.n_shards > 1:
            self.mesh = shd.stream_mesh(self.devices)
            # warm the default tenant's cache eagerly (construction-time
            # trace, matching the single-tenant behaviour tests pin)
            self._cores_for(client)
        else:
            self.mesh = None

    def resolve(self, job):
        """The client whose keys/nonce lease this job runs under."""
        if job.tenant is None or self._client_for is None:
            return self.client
        return self._client_for(job.tenant)

    def _cores_for(self, client):
        """(enc, dec) cores for one tenant's client on this stream's
        devices, built on first use and cached on the client itself."""
        if self.n_shards == 1:
            return client.encrypt_core, client.decrypt_core
        table = client.__dict__.setdefault("_stream_sharded_cores", {})
        key = tuple(d.id for d in self.devices)
        cores = table.get(key)
        if cores is None:
            cores = (self._sharded_enc_core(client),
                     self._sharded_dec_core(client))
            table[key] = cores
        return cores

    # --- shard_map'ed cores (multi-device groups) ---------------------------

    def _sharded_enc_core(self, client):
        impl = client.encrypt_impl
        n_ops = client.n_encrypt_operands

        def local(*args):
            *ops, n0 = args
            return impl(*ops, kops.shard_nonce_base(n0, ops[0].shape[0]))

        return jax.jit(jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(P("batch"),) * n_ops + (P(),),
            out_specs=P("batch"), check_vma=False))

    def _sharded_dec_core(self, client):
        impl = client.decrypt_impl

        def local(c0, c1, scale):
            return impl(c0, c1, scale)

        return jax.jit(jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(P("batch"), P("batch"), P("batch")),
            out_specs=P("batch"), check_vma=False))

    # --- placement ----------------------------------------------------------

    def _place(self, x):
        if self.mesh is not None:
            return jax.device_put(
                x, shd.batch_stack_sharding(self.mesh, jnp.ndim(x)))
        return jax.device_put(x, self.devices[0])

    # --- launches (async: no blocking here) ---------------------------------

    def launch(self, job):
        client = self.resolve(job)
        enc, dec = self._cores_for(client)
        if isinstance(job, EncJob):
            if job.messages.shape[1] != client.ctx.params.n_slots:
                raise ValueError(
                    f"tenant-purity violation: job for lane {job.tenant!r} "
                    f"carries {job.messages.shape[1]}-slot messages but the "
                    f"lane's parameter set has n_slots="
                    f"{client.ctx.params.n_slots}")
            ops = client.encrypt_operands(job.messages)
            c0, c1 = enc(*[self._place(o) for o in ops],
                         jnp.uint32(job.nonce0))
            return bucket_rows(c0, job.n_real), bucket_rows(c1, job.n_real)
        assert isinstance(job, DecJob)
        return dec(self._place(job.cts.c0),
                   self._place(job.cts.c1),
                   self._place(jnp.asarray(job.scales)))


class DualStreamScheduler:
    """Maps batch jobs onto the stream executors, round by round, with the
    analytic scheduler's mode policy, and records the dispatch log.

    Failure story: ``faults`` (a ``FaultInjector``) is probed at every
    launch and materialize; a stream whose launch raises is marked dead
    (``mark_failed``), its job is re-queued at the FRONT of its kind's
    queue (same job object, same nonce lease — the retried ciphertexts
    stay bit-identical), and subsequent rounds plan over the surviving
    streams only. ``events`` (an ``EventLog``) records every failure,
    re-queue and degradation so tests can replay the recovery.
    """

    def __init__(self, client, devices=None, n_streams: int | None = None,
                 oversubscribe: bool = False, faults=None, events=None,
                 client_for=None, telemetry=None):
        groups = shd.stream_groups(devices, n_streams,
                                   oversubscribe=oversubscribe)
        self.streams = [StreamExecutor(client, g, i, client_for=client_for)
                        for i, g in enumerate(groups)]
        self.faults = faults
        self.telemetry = telemetry
        self.events = events if events is not None else EventLog()
        self._alive = [True] * len(self.streams)
        self.log: list[DispatchRecord] = []
        self._round = 0

    @property
    def n_streams(self) -> int:
        return len(self.streams)

    @property
    def pad_multiple(self) -> int:
        """Devices per stream group — the batcher pads buckets to this so
        every batch axis divides every stream's mesh."""
        return self.streams[0].n_shards

    # --- stream liveness ----------------------------------------------------

    @property
    def alive_streams(self) -> list[int]:
        return [i for i, a in enumerate(self._alive) if a]

    @property
    def n_alive(self) -> int:
        return sum(self._alive)

    def mark_failed(self, stream: int, detail: str = "") -> None:
        """Declare a stream dead; it takes no further launches. Records a
        ``stream_failed`` event (+ ``degraded`` on the 2->1 transition).
        Never raises — callers check ``n_alive`` to decide whether any
        work can still run."""
        if not self._alive[stream]:
            return
        self._alive[stream] = False
        self.events.record("stream_failed", stream=stream,
                           round=self._round, detail=detail)
        if self.n_alive == 1:
            self.events.record("degraded", stream=self.alive_streams[0],
                               round=self._round,
                               detail="single-stream operation")

    def revive_all(self) -> None:
        """Bring every stream back (deployment-level recovery seam; tests
        use it between fault scenarios)."""
        self._alive = [True] * len(self.streams)

    # --- launches -----------------------------------------------------------

    def launch_job(self, stream: int, job, attempt: int = 0):
        """Fault-seamed single-job launch on one stream (no log entry)."""
        if self.faults is not None:
            self.faults.on_launch(stream=stream, round=self._round, job=job)
        return self.streams[stream].launch(job)

    def dispatch(self, enc_jobs, dec_jobs):
        """Launch every pending job; returns ``(launched, undispatched)``
        with ``launched`` = [(record, job, out)] in launch order (``out``
        unmaterialized) and ``undispatched`` = jobs that could not launch
        because every stream died. Each round assigns ``core.scheduler``'s
        policy pick to the ALIVE streams and launches before the round is
        blocked on — with no failures the dispatch log is exactly
        ``plan_rounds(n_enc, n_dec, n_alive)`` and ``undispatched`` is
        empty. A launch that raises marks its stream dead and re-queues
        the job at the FRONT of its queue (same job, same nonce lease) for
        the surviving streams. A lowering or compile refusal is not a
        stream fault (``faults.is_stream_fault``) and propagates."""
        enc_q, dec_q = deque(enc_jobs), deque(dec_jobs)
        launched = []
        while enc_q or dec_q:
            alive = self.alive_streams
            if not alive:
                break
            kinds = policy.assign_streams(len(enc_q), len(dec_q),
                                          len(alive))
            mode = policy.round_mode(kinds)
            for stream, kind in zip(alive, kinds):
                q = enc_q if kind == "enc" else dec_q
                job = q.popleft()
                try:
                    out = self.launch_job(stream, job)
                except Exception as e:  # noqa: BLE001 — classified below
                    if not is_stream_fault(e):
                        raise
                    q.appendleft(job)
                    self.events.record(
                        "requeue", stream=stream, round=self._round,
                        rids=job.rids, detail=f"launch failed: {e}")
                    self.mark_failed(stream, detail=repr(e))
                    break               # re-plan the round over survivors
                rec = DispatchRecord(
                    round=self._round, stream=stream, kind=kind, mode=mode,
                    bucket=job.bucket, rids=job.rids, t_launch=now())
                close_annotation(job.annotation)
                self.log.append(rec)
                if self.telemetry is not None:
                    self.telemetry.on_launch(rec, job)
                launched.append((rec, job, out))
            else:
                # full round launched: count it by mode (a broken round
                # re-plans and is counted when it completes)
                if self.telemetry is not None:
                    self.telemetry.on_round(mode)
            self._round += 1
        return launched, list(enc_q) + list(dec_q)

    def relaunch(self, job, attempt: int):
        """Re-launch one failed job on the surviving streams (bounded-
        retry path; the job keeps its nonce lease so the retried rows are
        bit-identical). Returns (record, out). Tries each alive stream
        in turn, marking further failures dead as it goes; raises
        ``AllStreamsFailed`` when none survives."""
        kind = "enc" if isinstance(job, EncJob) else "dec"
        while True:
            alive = self.alive_streams
            if not alive:
                raise AllStreamsFailed(
                    f"no alive stream to retry job rids={job.rids}")
            stream = alive[0]
            try:
                out = self.launch_job(stream, job, attempt=attempt)
            except Exception as e:  # noqa: BLE001 — classified below
                if not is_stream_fault(e):
                    raise
                self.events.record(
                    "requeue", stream=stream, round=self._round,
                    rids=job.rids, attempt=attempt,
                    detail=f"retry launch failed: {e}")
                self.mark_failed(stream, detail=repr(e))
                continue
            rec = DispatchRecord(
                round=self._round, stream=stream, kind=kind,
                mode=policy.round_mode((kind,)), bucket=job.bucket,
                rids=job.rids, attempt=attempt, t_launch=now())
            self.log.append(rec)
            if self.telemetry is not None:
                self.telemetry.on_launch(rec, job)
                self.telemetry.on_round(rec.mode)
            self._round += 1
            return rec, out

    def check_materialize(self, rec: DispatchRecord, job) -> None:
        """Materialize-phase fault seam (called right before a result is
        blocked on; the injected 'result_error' failure shape)."""
        if self.faults is not None:
            self.faults.on_materialize(stream=rec.stream, round=rec.round,
                                       job=job)

    def clear_log(self):
        """Reset the dispatch log and round counter (telemetry window
        boundary; the log otherwise grows one record per job forever)."""
        self.log.clear()
        self._round = 0

    def modes_executed(self, start: int = 0):
        """[(mode, kinds)] per round from the dispatch log (from log entry
        ``start`` on) — directly comparable to ``plan_rounds`` output."""
        rounds: dict[int, list] = {}
        for rec in self.log[start:]:
            rounds.setdefault(rec.round, []).append(rec)
        out = []
        for r in sorted(rounds):
            recs = sorted(rounds[r], key=lambda x: x.stream)
            out.append((recs[0].mode, tuple(x.kind for x in recs)))
        return out
