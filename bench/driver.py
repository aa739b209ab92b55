"""Drive the client service through one cell: build it from the cell's
configuration and traffic mix, warm every shape the mix uses, then run
the measured window and collect what the metrics and the check read.

The window drives ``ClientService.submit_encrypt`` / ``submit_decrypt``
/ ``result`` and counts a request complete once its result is in host
memory: both ciphertext polynomials of an encryption, the decoded slots
of a decryption. An open-loop request is timed from when it was due, so
a late generator or a stall counts against the service.
"""

from __future__ import annotations

import dataclasses
import gc
import queue
import threading
import time

import numpy as np

import traffic
from reference import Reference

# nonces of the two-limb ciphertexts a run decrypts: far above any the
# service leases in a run, so no encryption randomness is reused
DECRYPT_POOL_NONCE = 1 << 26
# spans the service keeps in a traced run: a run's worth, for the span
# metrics. An untraced run keeps the service's default ring, since every
# span kept is more for the garbage collector to walk inside the window.
TRACE_CAPACITY = 1 << 17
COLLECT_GRACE_S = 60.0            # how long past the window a result may come
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class ConfigMismatch(RuntimeError):
    """The program built a different deployment than the file states."""


@dataclasses.dataclass
class Done:
    """One request of the window."""
    index: int                    # request index in the schedule
    kind: str
    rid: int
    t_due: float                  # monotonic: due (open) or sent (closed)
    t_done: float | None = None   # result in host memory; None if failed
    error: str | None = None


@dataclasses.dataclass
class Window:
    """What one measured window produced."""
    t0: float
    t1: float
    requests: list                # [Done] sent in the window
    samples: dict                 # kind -> [(Done, host result)]
    lateness_s: list              # open loop: send time - due time
    spans: list                   # service spans of the window
    dispatch: list                # service DispatchRecords of the window
    nonces: dict                  # rid -> nonce, for encryptions
    compiles: int                 # compile events inside the window
    jit_entries_added: int        # jit-cache entries added by the window
    memory_peak_bytes: int | None
    gc_pauses_s: list             # full (generation 2) collections in it
    trace_s: float | None = None  # traced: start_trace to stop_trace


def service_params(config: dict):
    from repro.core.context import CKKSParams
    return CKKSParams(logn=int(config["logn"]), n_limbs=int(config["n_limbs"]),
                      decrypt_limbs=int(config["decrypt_limbs"]),
                      delta_bits=int(config["delta_bits"]),
                      p_bw=int(config["p_bw"]), seed=int(config["seed"], 16))


def build_service(config: dict, mix: dict, keep_spans: bool = False):
    """The service exactly as the configuration and the mix state it;
    ``keep_spans`` keeps every span of a run."""
    from repro.core.context import get_context
    from repro.fhe_client.client import FHEClient
    from repro.fhe_client.service.service import ClientService
    params = service_params(config)
    moduli = list(get_context(params).q_list)
    if moduli != [int(q) for q in config["moduli"]]:
        raise ConfigMismatch(f"the program's moduli {moduli} are not the "
                             f"configuration's")
    client = FHEClient(params, pipeline=config["pipeline"],
                       datapath=config["datapath"])
    svc = mix["service"]
    extra = {"trace_capacity": TRACE_CAPACITY} if keep_spans else {}
    return ClientService(client=client, buckets=tuple(svc["buckets"]),
                         fire_mode=svc["fire_mode"],
                         max_wait_s=float(svc["max_wait_s"]), **extra)


class Pools:
    """The messages and two-limb ciphertexts of one run, from its seed.
    The reference makes the ciphertexts, as a server would return them;
    ``reference_s`` is the time it takes, which set-up does not count."""

    def __init__(self, config: dict, mix: dict, seed: int):
        n_slots = (1 << int(config["logn"])) // 2
        kinds = traffic.kinds_used(mix)
        self.messages = traffic.messages(
            seed, int(mix.get("message_pool", 1)), n_slots) \
            if "enc" in kinds else None
        self.ciphertexts = []
        self.reference_s = 0.0
        if "dec" in kinds:
            t = time.monotonic()
            ref = Reference(config, limbs=2)
            plain = traffic.messages(seed, int(mix.get("decrypt_pool", 1)),
                                     n_slots, purpose="decrypt-messages")
            delta = float(2 ** int(config["delta_bits"]))
            for k, z in enumerate(plain):
                c0, c1 = ref.encrypt(z, DECRYPT_POOL_NONCE + k, limbs=2)
                self.ciphertexts.append((c0.astype(np.uint32),
                                         c1.astype(np.uint32), delta))
            self.reference_s = time.monotonic() - t

    def payload(self, kind: str, entry: int):
        if kind == "enc":
            return self.messages[entry]
        return self.ciphertexts[entry]


def to_host(kind: str, result):
    """A result as the user reads it: host arrays."""
    if kind == "enc":
        return np.asarray(result.c0), np.asarray(result.c1)
    return np.asarray(result)


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Runner:
    """One cell's service, pools and schedule for one run."""

    def __init__(self, config: dict, mix: dict, seed: int, seconds: float,
                 service=None, keep_spans: bool = False):
        self.config, self.mix = config, mix
        self.seed, self.seconds = int(seed), float(seconds)
        self.svc = service if service is not None \
            else build_service(config, mix, keep_spans)
        self.pools = Pools(config, mix, seed)
        self.schedule = traffic.schedule(mix, seed, seconds)
        checks = mix.get("check", {})
        strata = int(mix.get("check_strata", 1))
        self.reservoirs = {k: traffic.Reservoir(int(checks.get(k, 0)), seed,
                                                f"check-{k}", strata)
                           for k in traffic.KINDS}
        self._compiles = 0
        self._armed = False
        self._gc_pauses: list[float] = []
        self._gc_start = 0.0
        from repro.fhe_client.service.faults import RequestFailed
        self._failures = (RequestFailed, TimeoutError)
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_kw):
        if self._armed and event in COMPILE_EVENTS:
            self._compiles += 1

    def _on_gc(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._gc_start = time.monotonic()
        elif self._armed:
            self._gc_pauses.append(time.monotonic() - self._gc_start)

    def submit(self, i: int) -> tuple[int, str]:
        kind = self.schedule.kind(i)
        payload = self.pools.payload(kind, self.schedule.entry(i))
        with _annotate("bench.submit"):
            if kind == "enc":
                return self.svc.submit_encrypt(payload), kind
            return self.svc.submit_decrypt(payload), kind

    def fetch(self, done: Done, timeout: float | None):
        """Wait for one result and bring it to host memory."""
        try:
            with _annotate("bench.result"):
                host = to_host(done.kind, self.svc.result(done.rid,
                                                          timeout=timeout))
        except self._failures as e:
            done.error = repr(e)
            return None
        done.t_done = time.monotonic()
        return host

    # --- set-up ---------------------------------------------------------

    def warm_up(self) -> None:
        """Compile every (kind, bucket) the mix uses, and nothing else,
        through the service's own path."""
        for kind in traffic.kinds_used(self.mix):
            for bucket in self.svc.batcher.buckets:
                rids = []
                for i in range(bucket):
                    payload = self.pools.payload(kind, i % (
                        len(self.pools.messages) if kind == "enc"
                        else len(self.pools.ciphertexts)))
                    rids.append(self.svc.submit_encrypt(payload)
                                if kind == "enc"
                                else self.svc.submit_decrypt(payload))
                self.svc.flush()
                for rid in rids:
                    to_host(kind, self.svc.result(rid))

    # --- the window -----------------------------------------------------

    def run(self, trace_dir: str | None = None) -> Window:
        from repro.telemetry import jit_cache_entries
        import jax
        svc = self.svc
        svc.reset_telemetry()
        # every window starts from the same collector state, whatever
        # set-up left behind (a cold compile leaves far more than a load)
        gc.collect()
        self._gc_pauses = []
        gc.callbacks.append(self._on_gc)
        nonce0 = svc.client.nonce
        jit0 = jit_cache_entries(svc.lane_clients())
        svc.start()
        if trace_dir is not None:
            # device ops and TraceMe spans, no per-call Python tracing
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        self._armed = True
        t0 = time.monotonic()
        if self.mix["loop"] == "closed":
            requests, lateness = self._closed(t0, trace_dir)
        else:
            requests, lateness = self._open(t0, trace_dir)
        t1 = t0 + self.seconds
        svc.stop(drain=True)
        self._armed = False
        gc.callbacks.remove(self._on_gc)
        jit_added = jit_cache_entries(svc.lane_clients()) - jit0
        stats = jax.devices()[0].memory_stats() or {}
        spans = [s for s in svc.telemetry.tracer.spans()
                 if t0 <= (s.t("submit") or -1.0) < t1]
        dispatch = list(svc.dispatch_log)
        return Window(
            t0=t0, t1=t1, requests=requests,
            samples={k: r.items for k, r in self.reservoirs.items()},
            lateness_s=lateness, spans=spans,
            dispatch=[d for d in dispatch if t0 <= d.t_launch < t1],
            nonces=self._nonces(dispatch, nonce0), compiles=self._compiles,
            jit_entries_added=jit_added,
            memory_peak_bytes=stats.get("peak_bytes_in_use"),
            gc_pauses_s=list(self._gc_pauses),
            trace_s=(self._trace_stop - t0) if trace_dir else None)

    @staticmethod
    def _nonces(dispatch, nonce0: int) -> dict:
        """The nonce each encryption ran under, read from the service:
        every encrypt job leases its whole padded bucket from the client's
        counter, in launch order (a retry keeps its job's lease)."""
        out, nonce = {}, nonce0
        for rec in dispatch:
            if rec.kind != "enc" or rec.attempt:
                continue
            for r, rid in enumerate(rec.rids):
                out[rid] = nonce + r
            nonce += rec.bucket
        return out

    def _stop_trace(self, trace_dir, state: dict) -> None:
        if trace_dir is not None and not state.get("stopped"):
            import jax
            self._trace_stop = time.monotonic()
            jax.profiler.stop_trace()
            state["stopped"] = True

    def _closed(self, t0: float, trace_dir):
        """``outstanding`` requests in flight; each completion inside the
        window sends the next. Requests still in flight at the close are
        waited for, and not counted."""
        t_end = t0 + self.seconds
        pending, requests, state = [], [], {}
        i = 0
        for _ in range(int(self.mix["outstanding"])):
            rid, kind = self.submit(i)
            done = Done(i, kind, rid, time.monotonic())
            pending.append(done)
            requests.append(done)
            i += 1
        head = 0
        while head < len(pending):
            done = pending[head]
            head += 1
            host = self.fetch(done, timeout=None)
            if host is not None and done.t_done < t_end:
                self.reservoirs[done.kind].offer((done, host), done.index)
            if time.monotonic() < t_end:
                rid, kind = self.submit(i)
                new = Done(i, kind, rid, time.monotonic())
                pending.append(new)
                requests.append(new)
                i += 1
            elif not state.get("closed"):
                # the window is over: fire what waits for a full bucket
                state["closed"] = True
                self._stop_trace(trace_dir, state)
                self.svc.flush()
        self._stop_trace(trace_dir, state)
        return requests, []

    def _open(self, t0: float, trace_dir):
        """Requests sent at their due times from a thread of their own;
        one collector per kind takes results in order (each kind's queue
        is served first in, first out)."""
        sched = self.schedule
        t_end = t0 + self.seconds
        queues = {k: queue.Queue() for k in traffic.KINDS}
        requests, lateness = [], []

        def send():
            for i in range(len(sched)):
                due = t0 + float(sched.due[i])
                delay = due - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                rid, kind = self.submit(i)
                lateness.append(time.monotonic() - due)
                done = Done(i, kind, rid, due)
                requests.append(done)
                queues[kind].put(done)
            for q in queues.values():
                q.put(None)

        def collect(kind):
            while True:
                done = queues[kind].get()
                if done is None:
                    return
                left = t_end + COLLECT_GRACE_S - time.monotonic()
                host = self.fetch(done, timeout=max(left, 0.001))
                if host is not None:
                    self.reservoirs[kind].offer((done, host), done.index)

        threads = [threading.Thread(target=send, name="bench-send")]
        threads += [threading.Thread(target=collect, args=(k,),
                                     name=f"bench-collect-{k}")
                    for k in traffic.KINDS]
        for t in threads:
            t.start()
        time.sleep(max(t_end - time.monotonic(), 0.0))
        self._stop_trace(trace_dir, {})
        for t in threads:
            t.join(timeout=t_end + 2 * COLLECT_GRACE_S - time.monotonic())
        if any(t.is_alive() for t in threads):
            raise RuntimeError("the load generator did not finish")
        return sorted(requests, key=lambda d: d.index), lateness
