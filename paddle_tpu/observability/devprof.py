"""Per-program device-time profiling plane (ISSUE 17 tentpole).

Every telemetry layer before this one measures the HOST — spans, compile
events, HBM budgets, fleet skew, in-program dynamics. None of them can
say how many device-seconds each compiled program actually consumes, or
whether a given program is compute- or memory-bound. This module closes
that gap by joining two sources, both keyed by the PR-8 compile-ledger
program key (``train.step``, ``serve.decode_block[k8,s...]``, ...):

- **static cost**: at analysis time the memory ledger harvests
  ``compiled.cost_analysis()`` next to ``memory_analysis()`` — FLOPs and
  bytes accessed per program (:meth:`MemoryLedger.analyze`);
- **measured device time**: on a sampling cadence
  (``PADDLE_DEVPROF_SAMPLE_EVERY``), the dispatch sites stamp a
  pre-dispatch clock and call :meth:`DevProfPlane.tick` with the
  program's output arrays. On-cadence ticks ``block_until_ready`` HERE —
  the one place a timed-dispatch device sync is legal (the
  ``devprof-seam`` analysis rule pins every other ``block_until_ready``
  in the tree) — and record wall-from-dispatch as the program's device
  time. Off-cadence ticks are one counter increment; the serving decode
  path stays fully async between samples.

From the join the plane derives, per program: achieved FLOP/s, achieved
HBM bandwidth, arithmetic intensity, MFU, and a **roofline verdict** —
``compute-bound`` when the program's arithmetic intensity sits above the
hardware knee (peak FLOP/s ÷ peak bytes/s), ``memory-bound`` below it,
and ``host-bound`` when measured device time dwarfs what the roofline
says the program should cost (the dispatch path, not the chip, is the
bottleneck). Hardware knees come from the device kind with
``PADDLE_DEVPROF_PEAK_FLOPS`` / ``PADDLE_DEVPROF_PEAK_BW`` overrides
(CPU CI has no HBM — same pattern as ``PADDLE_HBM_CAPACITY_BYTES``).

Aggregations: a serving decode budget (device-seconds per emitted token,
per bucket/chunk program signature — the paged-vs-dense gap program by
program) and a training step split that reconciles measured step device
time against the PR-11 compute-vs-collective-wait attribution.

Cost contract (the PR-2 discipline, asserted in tests/test_devprof.py):
disabled (``PADDLE_DEVPROF`` unset) the hot paths pay one
module-attribute-is-None check; enabled, between samples, one dict
counter increment; the sync itself happens at most once per cadence
window per call-site context.

Surfaces: ``/perfz`` (statusz), ``serving_report()["devprof"]``,
``devprof.*`` metrics, the fleet snapshot block (the aggregator flags a
rank whose per-program device time diverges from the fleet median — a
sick chip, not a slow host), and per-program rows in both benches'
``BENCH_trajectory.jsonl`` records so the trajectory guard can name
WHICH program regressed.

jax is imported lazily inside the sampling seam — the observability
package stays stdlib-only at import time.
"""
import math
import threading
import time

from ..utils.envs import env_bool, env_float, env_int
from .metrics import registry as _registry

__all__ = ["DevProfPlane", "arm_from_env", "enable", "disable", "enabled",
           "plane", "report", "serving_block", "fleet_block", "device_peaks",
           "DEVICE_PEAKS", "ENABLE_ENV",
           "EVERY_ENV", "PEAK_FLOPS_ENV", "PEAK_BW_ENV"]

#: master switch — unset/false = every hot path is one None check
ENABLE_ENV = "PADDLE_DEVPROF"
#: sampling cadence in dispatches per call-site context: at most one
#: timed (blocking) dispatch per window, the rest stay async
EVERY_ENV = "PADDLE_DEVPROF_SAMPLE_EVERY"
#: hardware peak FLOP/s override for the roofline/MFU denominators
PEAK_FLOPS_ENV = "PADDLE_DEVPROF_PEAK_FLOPS"
#: hardware peak HBM bytes/s override for the roofline knee
PEAK_BW_ENV = "PADDLE_DEVPROF_PEAK_BW"

#: THE peak table (bench.py reads it too): bf16 peak FLOP/s and HBM
#: bytes/s of one chip, keyed by jax's ``device_kind``. Source: Google
#: Cloud TPU documentation, the system-architecture page of each version
#: ("TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM). A kind that is not here
#: is an error, not a default — add its row with its source.
DEVICE_PEAKS = {
    "TPU v5 lite": (197e12, 819e9),   # v5e
    "TPU v5": (459e12, 2765e9),       # v5p
    "TPU v4": (275e12, 1228e9),
    "TPU v3": (123e12, 900e9),
    # NOT a device metric: nominal knees so the CPU test suite gets finite
    # roofline denominators. Nothing computed from this row is a
    # measurement of any chip.
    "cpu": (1e12, 100e9),
}

#: measured device time past this multiple of the roofline-predicted
#: time means the chip is idle most of the window: host-bound
_HOST_BOUND_RATIO = 10.0

#: the live plane — None means disabled and every hot path is the single
#: ``_PLANE is not None`` check (the watchdog/dynamics one-check pattern)
_PLANE = None
_plane_lock = threading.Lock()


def device_peaks(peak_flops=None, peak_bw=None):
    """(kind, peak FLOP/s, peak bytes/s) of ``jax.devices()[0]`` from
    DEVICE_PEAKS; explicit arguments, then the env overrides, replace the
    table's numbers. An unknown device kind raises."""
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise ValueError(
            f"no peak FLOP/s / bytes/s known for device kind {kind!r}: add "
            f"a row (with its source) to devprof.DEVICE_PEAKS")
    flops, bw = DEVICE_PEAKS[kind]
    flops = float(peak_flops if peak_flops is not None
                  else env_float(PEAK_FLOPS_ENV, flops))
    bw = float(peak_bw if peak_bw is not None
               else env_float(PEAK_BW_ENV, bw))
    return kind, max(flops, 1.0), max(bw, 1.0)


class DevProfPlane:
    """The process-wide sampler: per-context cadence counters, the
    per-program sample table, and the cost join that turns samples into
    roofline rows."""

    def __init__(self, sample_every=None, peak_flops=None, peak_bw=None):
        self.sample_every = max(1, int(sample_every) if sample_every
                                is not None else env_int(EVERY_ENV, 16))
        self.device_kind, self.peak_flops, self.peak_bw = device_peaks(
            peak_flops, peak_bw)
        self._lock = threading.Lock()
        #: dispatches since the last timed sample, per call-site context
        #: ("train", "serve.decode", ...) — cadence is per SITE so a busy
        #: decode loop cannot starve the train step of samples
        self._since = {}
        #: program key -> accumulated sample stats
        self._programs = {}
        self.started = time.time()

    # ---- the sampling seam -------------------------------------------------
    def tick(self, key, t0, arrays, tokens=0, context=None):
        """One dispatch of ``key`` whose outputs are ``arrays`` and whose
        pre-dispatch ``time.monotonic()`` stamp is ``t0``. Off cadence:
        one counter increment. On cadence: THE timed sync — wait for the
        program's outputs inside this module and bank wall-from-dispatch
        as device time. Returns True when this tick sampled."""
        ctx = context or key
        with self._lock:
            n = self._since.get(ctx, 0) + 1
            if n < self.sample_every:
                self._since[ctx] = n
                return False
            self._since[ctx] = 0
        import jax

        jax.block_until_ready(arrays)  # devprof-seam-ok (the one legal timed-dispatch sync; see module docstring)
        dev_s = time.monotonic() - t0
        if dev_s < 0:  # a bad caller clock must not poison the table
            return False
        self._record(key, dev_s, tokens)
        return True

    def _record(self, key, dev_s, tokens):
        key = str(key)
        with self._lock:
            rec = self._programs.get(key)
            if rec is None:
                rec = self._programs[key] = {
                    "samples": 0, "device_s": 0.0, "last_s": 0.0,
                    "min_s": math.inf, "max_s": 0.0, "tokens": 0}
            rec["samples"] += 1
            rec["device_s"] += dev_s
            rec["last_s"] = dev_s
            rec["min_s"] = min(rec["min_s"], dev_s)
            rec["max_s"] = max(rec["max_s"], dev_s)
            rec["tokens"] += int(tokens)
        _registry.counter(
            "devprof.samples",
            help="timed (blocking) devprof dispatch samples taken").inc()
        _registry.histogram(
            "devprof.sample_s",
            help="sampled dispatch-to-ready device wall per timed "
                 "dispatch").observe(dev_s)
        labels = {"program": key}
        _registry.gauge(
            "devprof.device_s", labels=labels,
            help="last sampled device-seconds per dispatch of this "
                 "program").set(round(dev_s, 9))
        if tokens:
            _registry.gauge(
                "devprof.device_s_per_token", labels=labels,
                help="last sampled device-seconds per emitted token for "
                     "this decode program").set(round(dev_s / tokens, 9))
        cost = self._cost(key)
        flops = (cost or {}).get("flops")
        if flops:
            _registry.gauge(
                "devprof.mfu", labels=labels,
                help="achieved FLOP/s over device peak at the last "
                     "sample of this program").set(
                round(flops / dev_s / self.peak_flops, 6))

    # ---- the cost join -----------------------------------------------------
    @staticmethod
    def _cost(key):
        """The ledgered cost_analysis row for ``key`` (None until the
        memory ledger has analyzed that program)."""
        try:
            from . import compilemem

            return compilemem.memory.program_cost(key)
        except Exception:
            return None

    def _row(self, key, rec):
        n = rec["samples"]
        mean_s = rec["device_s"] / n if n else 0.0
        row = {
            "samples": n,
            "device_s_total": round(rec["device_s"], 6),
            "device_s_mean": round(mean_s, 9),
            "device_s_last": round(rec["last_s"], 9),
            "device_s_min": round(rec["min_s"], 9),
            "device_s_max": round(rec["max_s"], 9),
        }
        if rec["tokens"]:
            row["tokens"] = rec["tokens"]
            row["device_s_per_token"] = round(
                rec["device_s"] / rec["tokens"], 9)
        cost = self._cost(key) or {}
        flops = cost.get("flops") or 0.0
        nbytes = cost.get("bytes") or 0.0
        if flops:
            row["flops"] = flops
        if nbytes:
            row["bytes"] = nbytes
        if mean_s <= 0:
            row["verdict"] = "unknown"
            return row
        if flops:
            row["achieved_flops_s"] = round(flops / mean_s, 3)
            row["mfu"] = round(flops / mean_s / self.peak_flops, 6)
        if nbytes:
            row["achieved_bw_bytes_s"] = round(nbytes / mean_s, 3)
            row["hbm_util"] = round(nbytes / mean_s / self.peak_bw, 6)
        if flops and nbytes:
            row["arith_intensity"] = round(flops / nbytes, 4)
        # roofline: what SHOULD this program cost on this chip?
        t_compute = flops / self.peak_flops
        t_mem = nbytes / self.peak_bw
        bound = max(t_compute, t_mem)
        if bound <= 0:
            row["verdict"] = "unknown"
        elif mean_s > _HOST_BOUND_RATIO * bound:
            row["verdict"] = "host-bound"
        elif t_compute >= t_mem:
            row["verdict"] = "compute-bound"
        else:
            row["verdict"] = "memory-bound"
        return row

    # ---- surfaces ----------------------------------------------------------
    def _table(self):
        with self._lock:
            return {k: dict(v) for k, v in self._programs.items()}

    def report(self, analyze=False, program=None):
        """The /perfz payload: per-program roofline rows plus the serving
        decode-token budget and the training step split. ``analyze=True``
        forces the (suppressed re-compile) cost harvest for programs the
        ledger has not analyzed yet; ``program`` filters rows by key
        prefix."""
        if analyze:
            try:
                from . import compilemem

                compilemem.memory.analyze()
            except Exception:
                pass
        rows = {}
        for key, rec in sorted(self._table().items()):
            if program and not key.startswith(program):
                continue
            rows[key] = self._row(key, rec)
        out = {
            "enabled": True,
            "sample_every": self.sample_every,
            "device": {
                "kind": self.device_kind,
                "peak_flops_s": self.peak_flops,
                "peak_bw_bytes_s": self.peak_bw,
                "roofline_knee": round(self.peak_flops / self.peak_bw, 3),
            },
            "programs": rows,
        }
        serving = self._serving_split(rows)
        if serving:
            out["serving"] = serving
        training = self._training_split(rows)
        if training:
            out["training"] = training
        return out

    @staticmethod
    def _serving_split(rows):
        """The decode device-time budget: device-seconds per emitted
        token, overall and per decode program signature — the
        paged-vs-dense gap, attributed program by program."""
        decode = {k: r for k, r in rows.items()
                  if k.startswith("serve.decode") and r.get("tokens")}
        if not decode:
            return None
        dev_s = sum(r["device_s_total"] for r in decode.values())
        tokens = sum(r["tokens"] for r in decode.values())
        return {
            "decode_device_s": round(dev_s, 6),
            "decode_tokens": tokens,
            "device_s_per_token": round(dev_s / tokens, 9) if tokens else None,
            "per_program": {k: {
                "device_s_per_token": r.get("device_s_per_token"),
                "mfu": r.get("mfu"),
                "verdict": r.get("verdict"),
            } for k, r in decode.items()},
        }

    @staticmethod
    def _training_split(rows):
        """The step split: measured step device time next to the PR-11
        compute-vs-collective-wait attribution, so "the step got slower"
        reconciles into "the chip got slower" vs "the ring got slower"."""
        train = {k: r for k, r in rows.items() if k.startswith("train.")}
        if not train:
            return None
        out = {"per_program": {k: {
            "device_s_mean": r["device_s_mean"],
            "mfu": r.get("mfu"),
            "verdict": r.get("verdict"),
        } for k, r in train.items()}}
        step = train.get("train.step")
        if step:
            out["step_device_s_mean"] = step["device_s_mean"]
            h = _registry.get("collective.wait_s")
            wait = h.mean if h is not None and h.count else None
            if wait is not None and step["device_s_mean"] > 0:
                out["collective_wait_s_mean"] = round(wait, 9)
                out["compute_fraction"] = round(
                    max(0.0, 1.0 - wait / step["device_s_mean"]), 6)
        return out

    def fleet_block(self):
        """The bounded per-rank snapshot block the aggregator medians
        across ranks: mean device-seconds per dispatch for the costliest
        programs. None until something has been sampled."""
        table = self._table()
        if not table:
            return None
        ranked = sorted(table.items(), key=lambda kv: kv[1]["device_s"],
                        reverse=True)[:16]
        return {
            "sample_every": self.sample_every,
            "programs": {k: round(v["device_s"] / v["samples"], 9)
                         for k, v in ranked if v["samples"]},
        }


# ---- module-level switches (the watchdog arm/disarm idiom) -----------------
def arm_from_env():
    """Install the plane when ``PADDLE_DEVPROF`` is truthy (idempotent —
    every TrainStep / serving engine constructor calls this). Returns
    the live plane or None."""
    global _PLANE
    if _PLANE is None and env_bool(ENABLE_ENV):
        with _plane_lock:
            if _PLANE is None:
                _PLANE = DevProfPlane()
    return _PLANE


def enable(sample_every=None, peak_flops=None, peak_bw=None):
    """Install a plane unconditionally (benches arm profiling AFTER their
    timed comparison phases this way). Replaces any live plane."""
    global _PLANE
    with _plane_lock:
        _PLANE = DevProfPlane(sample_every=sample_every,
                              peak_flops=peak_flops, peak_bw=peak_bw)
    return _PLANE


def disable():
    """Back to the disabled one-check state; sampled data is dropped."""
    global _PLANE
    with _plane_lock:
        _PLANE = None


#: test hook — same contract as the other observability _reset()s
_reset = disable


def enabled():
    return _PLANE is not None


def plane():
    """The live plane or None."""
    return _PLANE


def report(analyze=False, program=None):
    """The /perfz payload ({"enabled": False} while disarmed)."""
    p = _PLANE
    if p is None:
        return {"enabled": False}
    return p.report(analyze=analyze, program=program)


def serving_block():
    """The serving_report()["devprof"] block: full report, no forced
    analysis (a report scrape must never trigger re-compiles)."""
    p = _PLANE
    if p is None:
        return {"enabled": False}
    return p.report(analyze=False)


def fleet_block():
    """The per-rank fleet-snapshot block (None while disarmed or before
    the first sample)."""
    p = _PLANE
    if p is None:
        return None
    return p.fleet_block()
