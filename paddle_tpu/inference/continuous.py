"""Continuous-batching serving engine over the paged KV pool (reference
capability: paddle/fluid/inference AnalysisPredictor's serving class +
PaddleNLP block-attention / vLLM-style continuous batching; PAPERS.md
ragged-paged-attention).

TPU-native shape: compute is two jitted programs with STATIC shapes per
sampling configuration — a MIXED step (one packed [T]-token forward that
carries every pending prompt chunk and every decode row through the ragged
paged cache, then scans the remaining decode_block-1 decode steps) and a
decode BLOCK (decode_block steps over all `max_seqs` slots through paged
cache entries; kernel-backed paged attention on TPU). Prompt length is a
runtime operand of the mixed step, not a compile-time bucket. The
scheduler is plain host Python between jitted calls: retire finished
sequences, free their pages, admit queued requests into freed slots
mid-flight of everyone else — the continuous part. Memory is bounded by
the page pool, not by max_seqs × max_len:

- admission is reservation-based and does no device work: a request
  enters only when ceil((true_len + max_new) / page_size) pages are free,
  so decode can never deadlock on pool exhaustion;
- page 0 is scratch: inactive slots' page tables point at it, their
  writes land there harmlessly (lengths masks it out of every real row).

Decoding is greedy by default; serve(do_sample=True, ...) runs the dense
path's sampler math with per-request key streams (reproducible regardless
of co-scheduling). kv_cache_dtype="int8" switches the pool to the
QuantizedTensor layout the Pallas kernel consumes natively.

Data-plane pipeline (ISSUE 6): the engine overlaps host scheduling with
device compute instead of ping-ponging between them —

- **chunked prefill** (``prefill_chunk=``): the mixed step's budget of
  prompt tokens. A long prompt streams into the pool at most that many
  tokens a dispatch, shortest remainder first, beside everyone's decode
  rows, so a 2048-token prompt does not stall every co-tenant's TPOT.
  A mid-prefill slot's scan rows are routed to the scratch page, so the
  decode steps of a dispatch cannot write into half-built pages.
- **double-buffered async decode** (``async_decode=``): decode block k+1
  is dispatched chained off block k's device-resident last-token row
  BEFORE block k's tokens are read back; the host retire/admit/emit work
  for block k runs under block k+1's device execution. Lengths and key
  indices advance at dispatch time (identical to emit-time accounting for
  every surviving slot — retired slots are zeroed anyway), retirement and
  admission stay at readback points, and the in-flight depth is bounded
  at ONE so pool donation stays a single-owner chain.
- **lock decomposition**: jitted EXECUTION serializes per engine
  (``engine.dispatch_lock``); only first-TRACE of a program key takes the
  process-wide ``_COMPILE_LOCK`` (concurrent tracing of the shared
  model's programs leaks tracers through the framework's thread-oblivious
  Tensor state — executing already-compiled programs does not). N
  in-process replicas therefore genuinely run concurrently once warm.
"""
import functools
import hashlib
import itertools
import math
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import core as _core
from ..framework.core import Tensor
from ..generation import _make_sampler
from ..observability import compilemem as _compilemem
from ..observability import devprof as _devprof
from ..observability import goodput as _goodput
from ..observability import tracing as _trace
from ..observability.metrics import registry as _registry
from ..ops.paged_attention import KVCacheSpec
from ..testing import chaos
from ..utils.envs import env_int as _env_int
from ..utils.metrics_bus import counters
from ..utils.retry import RetryPolicy

# serving telemetry (the Gemma-on-TPU serving comparison's vocabulary,
# PAPERS.md): TTFT = serve-entry → first token per request; TPOT = one
# block's own interval / tokens in the block (see _process_block). Gauges
# carry high-water marks so a post-hoc snapshot still shows peak pressure.
# Always-on: per-request / per-dispatch observes are noise against a jitted
# model call.
_M_TTFT = _registry.histogram("serve.ttft_s")
_M_TPOT = _registry.histogram(
    "serve.tpot_s",
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
             0.05, 0.1, 0.25, 0.5, 1.0, 2.5))
_M_QUEUE = _registry.gauge("serve.queue_depth")
_M_OCCUPANCY = _registry.gauge("serve.slot_occupancy")
_M_TOKENS = _registry.counter("serve.tokens_out")
_M_REQUESTS = _registry.counter("serve.requests")
_M_PREFIX_HIT = _registry.counter("serve.prefix.hit_pages")
_M_PREFIX_LOOKUP = _registry.counter("serve.prefix.lookup_pages")
# data-plane pipeline metrics (ISSUE 6): prefill chunks landed between
# decode blocks, and warmup()'s AOT compile wall (the spike the per-replica
# warmup keeps out of first requests). What the pipeline hides of the host
# is read off the step log (observability/tracing.py).
_M_CHUNKS = _registry.counter("serve.prefill_chunks")
_M_WARMUP = _registry.histogram("serve.compile_warmup_s")
# page-pool fragmentation gauges (ISSUE 8): where the pool's pages are —
# truly free, held by the prefix cache (evictable), or referenced by
# in-flight requests — plus the cache-held fraction of reclaimable pages.
# The HBM ledger's kv_pool component says how BIG the pool is; these say
# how USED it is.
_M_POOL_FREE = _registry.gauge(
    "serve.pool_frag_free_pages", help="KV pool pages on the free list")
_M_POOL_EVICT = _registry.gauge(
    "serve.pool_frag_evictable_pages",
    help="refcount-0 prefix-cache pages (reclaimable, LRU-evictable)")
_M_POOL_USED = _registry.gauge(
    "serve.pool_frag_used_pages",
    help="pages referenced by in-flight requests")
_M_POOL_FRAG = _registry.gauge(
    "serve.pool_frag_ratio",
    help="cache-held fraction of reclaimable pages "
         "(evictable / (free + evictable))")

# one module-level jitted block-decode key builder (jit cache survives
# across serve() calls) over PER-REQUEST key bases (online mode admits
# requests with different seeds into one batch): bases [max_seqs, 2],
# idxs [k, max_seqs] -> keys [k, max_seqs, 2]. fold_in(fold_in(base, rid), i)
# == fold_in(key_base, i) with key_base = fold_in(base, rid), so the sampled
# streams are bit-identical to the pre-online single-seed
# fold_in(fold_in(seed_key, rid), i) scheme.
_KEYS_FROM_BASE = _compilemem.ledgered_jit(jax.vmap(
    jax.vmap(lambda kb, i: jax.random.fold_in(kb, i), in_axes=(0, 0)),
    in_axes=(None, 0)), key="serve.keys_from_base")

class _StampedRLock:
    """RLock that remembers WHEN its current outermost hold began.

    The serving monitor needs to tell apart two reasons a dispatcher's
    heartbeat goes stale while the process-wide dispatch lock is busy:
    the holder is legitimately inside a long first-compile (every other
    dispatcher queues behind it — nobody is dead), or the holder is wedged
    in a hung device call (nothing will ever progress — the stale replicas
    ARE dead and their work must relocate). A bare try-acquire can't
    distinguish them; the hold-start timestamp can: a hold younger than
    the hang deadline reads as compiling, older reads as wedged.

    It also tracks WHO participates — the holder's thread ident and the
    idents blocked in acquire() — so the monitor only credits the lock for
    a replica's silence when that replica's dispatcher is actually the
    holder or a waiter. A dispatcher wedged somewhere ELSE (post-lock host
    sync, a blocking user callback) must not ride out its death verdict on
    other threads' healthy compiles."""

    __slots__ = ("_lock", "_depth", "_since", "_holder", "_waiters")

    def __init__(self, name=None):
        self._lock = threading.RLock()
        if name is not None:
            # label for the runtime lock-order sanitizer
            # (testing/lockorder.py): the compile lock and every engine's
            # dispatch lock are all born on the line above, and the
            # sanitizer must keep them distinct order classes. A plain C
            # RLock (sanitizer off) has no __dict__ — stamping is free to
            # fail.
            try:
                self._lock._lo_name = name
            except AttributeError:
                pass
        self._depth = 0
        self._since = None  # monotonic start of the current outermost hold
        self._holder = None   # thread ident of the current holder
        self._waiters = set()  # thread idents blocked in acquire()

    def acquire(self, blocking=True, timeout=-1):
        me = threading.get_ident()
        if blocking and self._holder != me:  # a reentrant acquire can't block
            self._waiters.add(me)  # set ops are atomic under the GIL
            try:
                # holder bookkeeping runs INSIDE the waiter window: a gap
                # where the winning thread is neither waiter nor holder
                # would let the monitor sample participants() in between
                # and kill a healthy replica that just won the lock
                return self._acquired(me, self._lock.acquire(blocking,
                                                             timeout))
            finally:
                self._waiters.discard(me)
        return self._acquired(me, self._lock.acquire(blocking, timeout))

    def _acquired(self, me, ok):
        if ok:
            self._depth += 1
            if self._depth == 1:
                self._since = time.monotonic()
                self._holder = me
        return ok

    def release(self):
        # fields mutate only while the lock is held (single writer); the
        # monitor's unlocked held_since()/participants() reads are benign
        # torn-free races
        self._depth -= 1
        if self._depth == 0:
            self._since = None
            self._holder = None
        self._lock.release()

    __enter__ = acquire

    def __exit__(self, *exc_info):
        self.release()

    def held_since(self):
        """Monotonic timestamp of the current outermost acquire, or None
        when free. Advisory (read without the lock)."""
        return self._since

    def participants(self):
        """Thread idents currently holding OR blocked acquiring the lock.
        Advisory snapshot (read without the lock)."""
        out = set(self._waiters)
        holder = self._holder
        if holder is not None:
            out.add(holder)
        return out


#: Process-wide COMPILE lock: the serving frontend drives one engine per
#: dispatcher THREAD, and concurrent jit TRACING of the shared model's
#: programs leaks tracers through the framework's (thread-oblivious)
#: Tensor state. Only first-trace needs the global lock — each engine's
#: program keys are explicit (bucket/sampling/k), so once a key has run
#: successfully every later call is a jit cache hit executing compiled
#: code, which is thread-safe. Execution serializes per engine on
#: ``engine.dispatch_lock`` instead (the engine is single-threaded by
#: contract; the per-engine lock exists so the frontend's liveness
#: monitor can tell a dispatcher wedged in a device call from one queued
#: behind a neighbor's compile). This replaces the pre-ISSUE-6
#: process-wide ``_DISPATCH_LOCK`` that serialized every jitted call of
#: every replica behind one lock.
_COMPILE_LOCK = _StampedRLock(name="inference.compile_lock")
_ENGINE_SEQ = itertools.count()  # `engine` of a step record
#: the spans of one dispatch as (span, first stamp, last stamp) over its
#: step record (docs/OBSERVABILITY.md "The step log"): the dispatch's life,
#: then its phases on the dispatcher thread
STEP_PHASES = (("serve.step", "t_step0", "t_emit1"),
               ("serve.pack", "t_pack0", "t_disp0"),
               ("serve.decode", "t_disp0", "t_disp1"),
               ("serve.decode.sync", "t_sync0", "t_ready"),
               ("serve.emit", "t_ready", "t_emit1"))

#: canonical greedy sampling tuple — every greedy request shares ONE
#: compiled prefill/decode program regardless of the knob values passed
GREEDY_SAMPLING = (False, 1.0, 0, 1.0)


def canonical_sampling(do_sample, temperature=1.0, top_k=0, top_p=1.0):
    return (GREEDY_SAMPLING if not do_sample else
            (True, float(temperature), int(top_k), float(top_p)))


class EngineRequest:
    """One request's full lifecycle state — the unit the online serving
    control plane (paddle_tpu/serving) hands to the engine and the engine
    hands back finished. ``serve()`` builds these internally, so the batch
    path and the frontend path exercise the SAME admission/decode/retire
    machinery.

    Result surface (the per-request failure-reason contract): exactly one of
    ``result`` (np.int32 array, prompt + generated tokens) or ``error`` (the
    exception that failed the request; ``error_message`` is its rendered
    string) is set once ``finished`` is True. ``timed_out`` requests retire
    with a partial ``result``.
    """

    __slots__ = ("rid", "prompt", "max_new_tokens", "eos_token_id",
                 "sampling", "seed", "timeout_s", "on_token", "adapter",
                 "tokens", "n_generated", "n_dispatched", "last_token",
                 "pages", "slot", "key_base", "t_enqueue", "t_admit",
                 "t_first_token", "t_done", "error", "result", "finished",
                 "timed_out", "cancelled", "trace")

    def __init__(self, rid, prompt, max_new_tokens, eos_token_id=None,
                 sampling=GREEDY_SAMPLING, seed=0, timeout_s=None,
                 on_token=None, adapter=None):
        self.rid = int(rid)
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        if self.max_new_tokens < 1:
            # admission always produces the prefill's first token, so a
            # 0-token budget can't be honored — reject it at construction
            # (submit()/serve() callers both reach this) instead of decoding
            # past the page reservation
            raise ValueError(
                f"request {self.rid}: max_new_tokens must be >= 1, got "
                f"{self.max_new_tokens}")
        self.eos_token_id = eos_token_id
        self.sampling = tuple(sampling)
        self.seed = int(seed)
        self.timeout_s = timeout_s
        self.on_token = on_token
        # resolved serving.adapters.LoRAAdapter (or None): the low-rank
        # LM-head delta this request decodes under. An object, never a
        # name — registry resolution/refcounting is the frontend's job
        self.adapter = adapter
        self.tokens = []          # prompt + generated, filled at admission
        self.n_generated = 0
        # tokens DISPATCHED to the device (>= n_generated while a decode
        # block is in flight): the async pipeline builds block k+1's key
        # indices and fed lengths from this before block k's tokens are
        # read back. For surviving slots it always equals what emit-time
        # accounting would produce; retired slots discard the overshoot.
        self.n_dispatched = 0
        self.last_token = None
        self.pages = []
        self.slot = None
        self.key_base = None      # np uint32[2], lazily built at admission
        self.t_enqueue = time.monotonic()  # TTFT epoch
        self.t_admit = None
        self.t_first_token = None
        self.t_done = None
        self.error = None
        self.result = None
        self.finished = False
        self.timed_out = False
        self.cancelled = False    # set by the frontend; honored at the next
        # block boundary (the request retires with a partial result)
        # request-scoped tracing (ISSUE 7): the frontend's per-attempt span
        # handle — the engine's admit/prefill/decode spans nest under it.
        # None on the batch serve() path / when telemetry is off; a reroute
        # clone gets the NEW attempt's span from the frontend.
        self.trace = None

    @property
    def error_message(self):
        """Failure reason as a string, or None (satellite: rid -> reason)."""
        if self.error is None:
            return None
        return f"{type(self.error).__name__}: {self.error}"

    def clone_for_retry(self):
        """A fresh, un-admitted copy for rerouting to another replica after
        this one's replica died mid-flight. Keeps rid/seed so the sampled
        key stream — hence the output — is identical on the new replica,
        and t_enqueue so TTFT/queue-wait span the whole journey including
        the time lost on the dead replica (the failover tail is exactly
        what the per-SLO histograms exist to expose)."""
        clone = EngineRequest(self.rid, self.prompt, self.max_new_tokens,
                              eos_token_id=self.eos_token_id,
                              sampling=self.sampling, seed=self.seed,
                              timeout_s=self.timeout_s,
                              on_token=self.on_token, adapter=self.adapter)
        clone.t_enqueue = self.t_enqueue
        return clone


def _row_sampler(do_sample, temperature, top_k, top_p):
    """Per-ROW sampler: each slot consumes its own PRNG key stream, so a
    sequence's sampled tokens do not depend on which other requests happen
    to share the batch (continuous batching reorders co-tenants freely).
    Reuses the dense path's sampler math (generation._make_sampler)."""
    base = _make_sampler(do_sample, temperature, top_k, top_p,
                         repetition_penalty=1.0, min_length=0,
                         eos_token_id=None)
    if not do_sample:
        return lambda logits, keys: base(logits, None)
    return jax.vmap(lambda lg, k: base(lg[None], k)[0])


class _PrefillState:
    """One slot mid-prefill: the full page reservation plus how many of
    the prompt's tokens already sit in the pool. The slot's page-table row
    is installed at admission; until graduation its row of the mixed
    step's scan table is zero, so the decode steps of a dispatch write
    this slot's fed token to the scratch page instead of into half-built
    pages."""

    __slots__ = ("req", "pages", "n_pre0", "digests", "consumed")

    def __init__(self, req, pages, n_pre, digests, consumed):
        self.req = req
        self.pages = pages          # full reservation (shared + new)
        self.n_pre0 = n_pre         # prefix-cache hit width at admission
        self.digests = digests      # prompt-page digest chain (for indexing)
        # prompt TOKENS already in the pool (token-granular: a chunk needs
        # no page alignment); starts past the prefix-cache hit
        self.consumed = consumed


class _InflightBlock:
    """One dispatched-but-not-read-back decode block: the device token
    array, the slot→request mapping frozen at dispatch time, the
    device-resident last-step row the NEXT block's feed chains from, and
    the dispatch's step record (completed at readback)."""

    __slots__ = ("blk", "last", "k", "rows", "step", "host", "cold")

    def __init__(self, blk, last, k, rows, step, host=None, cold=False):
        self.blk = blk      # device [k, max_seqs] token block
        self.last = last    # device [max_seqs, 1] last-step tokens
        self.k = k
        self.rows = rows    # [(slot, req)] active at dispatch
        self.step = step    # the step-log record under construction
        self.host = host    # sync mode: tokens already read back in-lock
        self.cold = cold    # dispatched under a first-trace (compile) hold


def _engine_init_phase(init):
    """The set-up log's `engine.init` (observability/tracing.py) round the
    engine's constructor. Not synced: the pools are still being filled when
    it returns, while the host goes on to trace the step programs."""

    @functools.wraps(init)
    def phased(self, *args, **kwargs):
        with _trace.setup_phase("engine.init", synced=False) as phase:
            init(self, *args, **kwargs)
            phase.counts["pool_bytes"] = self.pool_bytes()

    return phased


class ContinuousBatchingEngine:
    @_engine_init_phase
    def __init__(self, model, max_seqs=4, page_size=16, num_pages=None,
                 max_len=512, kv_cache_dtype=None, decode_block=8,
                 enable_prefix_cache=False, prefill_chunk=None,
                 async_decode=True, dispatch_lock=None, ragged=True):
        if ragged is not None and not ragged:
            # the keyword selects nothing. It is still taken because the
            # benchmark's workload files pass `"ragged": true` and only a
            # `benchmark` PR may edit them; it goes when they drop the key
            raise ValueError(
                "ragged=False named the bucket-ladder plane, which is "
                "deleted: the ragged plane is the engine")
        missing = [name for name in ("serving_trunk", "serving_head")
                   if not hasattr(model, name)]
        if missing:
            raise TypeError(
                f"{type(model).__name__} lacks {' and '.join(missing)}: the "
                "engine's step programs call a model as trunk and head (the "
                "serving protocol of models/llama.py and "
                "models/deepseek_v3.py)")
        cfg = model.config
        self.model = model
        model.eval()
        # device-time profiling plane (ISSUE 17): PADDLE_DEVPROF=1 samples
        # one timed decode dispatch per cadence window; disabled, the
        # dispatch path pays one is-None check
        _devprof.arm_from_env()
        self.max_seqs = max_seqs
        self.page_size = page_size
        self.max_len = max_len
        self.pages_per_seq = -(-max_len // page_size)  # page-table width
        # default pool = dense equivalent; callers size it down to the
        # expected occupancy — that is the memory win
        self.num_pages = num_pages or (1 + max_seqs * self.pages_per_seq)
        if self.num_pages < 2:
            raise ValueError("need at least one scratch + one real page")
        dtype = next(iter(model.parameters())).dtype
        self.kv_cache_dtype = kv_cache_dtype
        # the model says what its layers cache (the model protocol:
        # serving_cache_spec / serving_trunk / serving_head; the members
        # read here are stated in ops/cache_specs.py): K and V pools
        # (ops.paged_attention.KVCacheSpec, also the default for a model
        # that says nothing), one pool of latent rows
        # (ops.latent_pool.LatentCacheSpec), or a spec a LAYER
        # (ops.cache_specs.LayerCacheSpecs: selected K and V pages beside
        # slots of recurrent state). Pools, cache entries, bytes and what a
        # plane may do are the layer's; the allocator counts pages, which
        # every paged layer shares through the row's page table, and a
        # state slot is the row itself.
        spec = getattr(model, "serving_cache_spec", None)
        self._cache_spec = spec() if spec is not None else KVCacheSpec(
            cfg.num_hidden_layers, cfg.num_key_value_heads, cfg.head_dim,
            cfg.num_attention_heads)
        self._layer_specs = self._cache_spec.layers
        # token budget for prompt chunks per mixed dispatch (it also sizes
        # a window layer's ring of pages; every other spec ignores it)
        self._ragged_chunk = max(int(prefill_chunk or 0) or min(256, max_len), 1)
        with _trace.setup_phase("engine.init.pools"):
            self.pools = self._cache_spec.make_pools(
                self.num_pages, page_size, dtype, kv_cache_dtype,
                max_seqs=max_seqs, prefill_chunk=self._ragged_chunk)
        self.free_pages = list(range(1, self.num_pages))  # page 0 = scratch
        self.free_slots = list(range(max_seqs))
        self.page_table = np.zeros((max_seqs, self.pages_per_seq), np.int32)
        self.lengths = np.zeros(max_seqs, np.int32)
        self._insert_fns = {}    # the KV handoff plane's (adopt_request)
        self._decode_fns = {}
        self._decode_block_fns = {}
        # ---- automatic prefix caching (vLLM-class; PAPERS.md ragged paged
        # attention context). Content-addressed FULL prompt pages: a page
        # holding tokens [j*bs, (j+1)*bs) of some prompt is indexed by the
        # exact byte string of the prompt's first (j+1)*bs tokens, so a later
        # request sharing that prefix points its page table at the SAME page
        # (refcounted) and streams in only its suffix — the mixed step reads
        # the shared pages through the row's page table like any other
        # page. Pages with refcount 0 stay cached (LRU-evictable) until
        # the allocator needs them. Shared pages are never written: decode
        # writes at positions >= true_len and the match is capped at
        # (true_len-1)//bs pages, so every write lands in a private page.
        # ---- data-plane pipeline knobs (ISSUE 6) --------------------------
        # prefill_chunk: the mixed step's budget of prompt tokens a
        # dispatch, rounded down to whole pages; None/0 takes the default
        # budget below. It composes with every pool dtype: a token attends
        # through the pool whatever chunk wrote it, so the chunk size
        # cannot change an output.
        if prefill_chunk:
            prefill_chunk = max(int(prefill_chunk) // page_size, 1) * page_size
        self.prefill_chunk = int(prefill_chunk or 0)
        self.async_decode = bool(async_decode)
        # per-engine execution lock (injectable: engines handed one
        # instance serialize their jitted sections like the pre-ISSUE-6
        # process-wide lock did); first-trace additionally takes the
        # global _COMPILE_LOCK — see _locked_dispatch()
        self.dispatch_lock = dispatch_lock or _StampedRLock(
            name="inference.dispatch_lock")
        self._warm = set()          # program keys that have run successfully
        self._last_dispatch_cold = False  # last _locked_dispatch traced?
        self._prefilling = {}       # slot -> _PrefillState (mid-prefill)
        self._inflight = None       # the ONE in-flight _InflightBlock
        # requests retired while an out-of-band caller (export_pages'
        # _settle_inflight) processed the in-flight block: step() returns
        # them on its next call so the frontend still finishes every handle
        self._pending_retired = []
        # step log (observability/tracing.py): this engine's id in it, the
        # entry stamp of the running step() call, admissions since the last
        # record (bounded: a replica whose requests leave before a dispatch
        # of these planes — the prefill role — never drains them), the
        # previous block's readback stamp (the start of the next block's own
        # interval) and the dispatcher thread's open phase annotation
        self._engine_seq = next(_ENGINE_SEQ)
        self._t_step0 = 0
        self._admits = deque(maxlen=4 * self.max_seqs)
        self._t_prev_ready = 0
        self._phase_ann = None
        self.enable_prefix_cache = bool(enable_prefix_cache)
        if self.enable_prefix_cache and kv_cache_dtype == "int8":
            # a shared prefix would be re-read through the lossy int8
            # pool while the no-cache path attends to exact float KV —
            # silently divergent outputs near argmax ties; refuse rather
            # than break the engine's exact-equality contract
            raise ValueError("enable_prefix_cache does not compose with "
                             "kv_cache_dtype='int8' (lossy prefix KV would "
                             "change outputs vs the uncached path)")
        # hashed prefix-page index (ISSUE 6 satellite): keys are CHAINED
        # 16-byte blake2b digests — digest[j] = H(digest[j-1] || page j's
        # token bytes) — so indexing or probing a whole prompt costs
        # O(prompt bytes) total instead of the old O(pages^2) re-hash of
        # the full prefix per page (which made Router.place()'s affinity
        # probe quadratic in prompt length). A digest collision would
        # false-match foreign KV; at 128 bits that is beyond-cosmic-ray
        # territory, and tests assert the probe equals a content-exact
        # oracle over real workloads.
        self._prefix_index = {}   # chained page digest -> page_id
        self._page_hash = {}      # page_id -> digest (indexed pages)
        self._page_refs = {}      # page_id -> refcount (in-use pages)
        from collections import OrderedDict

        self._evictable = OrderedDict()  # page_id -> None; LRU order
        self._gather_fns = {}    # the KV handoff plane's (export_pages)
        self._cache_weights_version = None
        # decode_block: max decode steps fused into ONE device dispatch
        # (lax.scan). Each dispatch costs a full host→device round trip, so
        # per-token dispatch makes small-model serving host-bound. Trade-off:
        # retirement/admission (and on_token streaming) happen at block
        # boundaries, and a sequence hitting EOS mid-block wastes the rest of
        # the block's compute for its slot. 1 restores per-token behavior.
        self.decode_block = max(int(decode_block), 1)
        # observability for tests/bench: peak pages in use, deferred admits,
        # and the degradation counters (failed/timed-out requests keep their
        # co-tenants serving — see serve())
        self.stats = {"peak_pages": 0, "deferred_admissions": 0,
                      "decode_steps": 0, "prefix_hit_pages": 0,
                      "prefix_evictions": 0, "failed_requests": 0,
                      "timed_out_requests": 0,
                      # grid steps the mixed steps' attention calls walked a
                      # layer, and the steps of the dense walk (step log's
                      # `ragged_walk`, summed)
                      "ragged_walk": (0, 0),
                      # the same of the scan steps' decode calls, a layer
                      # and forward (step log's `paged_walk`, summed)
                      "paged_walk": (0, 0)}
        # per-serve map rid -> exception for requests that failed in
        # isolation (their results entry is None); the EngineRequest carries
        # the same exception + its rendered string for the online path.
        # Bounded so a long-running online engine can't grow it forever.
        self.request_errors = {}
        self._request_errors_bound = 1024
        # ---- online-serving state (frontend-driven mode) ------------------
        # slot -> EngineRequest. serve() uses the same machinery, so batch
        # and online requests share one admission/decode/retire path.
        self._active = {}
        # all co-scheduled requests share ONE sampling tuple (the sampler is
        # a compile-time constant of the decode program); admission defers
        # requests whose sampling differs from the running group's
        self._active_sampling = None
        # ---- per-request LoRA plane (ISSUE 19) ----------------------------
        # The decode group's adapter RANK is a compile-time constant of the
        # lora decode programs (like sampling); the adapter WEIGHTS are
        # runtime operands — per-row indices gather stacked [slots+1, ...]
        # A/B tensors inside the program, slot 0 all-zeros so no-adapter
        # rows ride along bit-identically (+0.0 delta). None = base group:
        # the untouched pre-LoRA programs, byte-for-byte.
        self._active_lora_rank = None
        self._slot_adapter = {}   # slot -> LoRAAdapter (adapter rows only)
        self._lora_slots = _env_int("PADDLE_LORA_SLOTS", 4)
        self._lora_device = OrderedDict()   # digest -> (a_dev, b_dev); LRU
        self._lora_stack_cache = OrderedDict()  # (rank, digests) -> stacks
        self._lora_decode_fns = {}
        self._lora_block_fns = {}
        self._lora_dims = (getattr(cfg, "hidden_size", None),
                           getattr(cfg, "vocab_size", None))
        # ---- the dispatch plane (ISSUE 20) --------------------------------
        # One packed [T]-token forward carries every prefill chunk AND every
        # decode row per step (ops/ragged_paged_attention.py): ONE mixed
        # program plus the fixed-k decode block per (sampling, kv-dtype,
        # lora-rank), whatever the prompt lengths.
        if self.enable_prefix_cache:
            # written for K and V pools: a cache that cannot share a row's
            # pages refuses by name
            self._refuse("prefix_cache",
                         "the prefix cache (enable_prefix_cache)")
        # packed token-stream width: chunk budget + one feed token per slot
        self._ragged_tokens = self._ragged_chunk + max_seqs
        self._ragged_fns = {}        # sampling -> mixed program
        self._lora_ragged_fns = {}   # (sampling, rank) -> mixed lora program
        # O(1) maintained pages-in-use counter (satellite: replaces the
        # derived scan; tests assert it equals the scan at quiet points)
        self._pages_in_use = 0
        # (mutation_version, state_dict) captured at the last admission;
        # step() reuses it so the TPOT-critical loop never pays the full
        # parameter-tree walk per decode block (the batch path captured
        # state once per serve() — this keeps the online path at parity)
        self._decode_state_cache = None
        # HBM budget ledger + OOM forensics (ISSUE 8): the KV page pool is
        # a first-class component of the device memory budget, and an OOM
        # report must say what the engine was serving when it died. Both
        # registrations are weak — a dropped engine vanishes from reports.
        _compilemem.memory.register_component_provider(
            "kv_pool", self, "pool_bytes")
        _compilemem.register_oom_context(
            "serving_engine", self, "_oom_context")

    def _oom_context(self):
        """Serving-state snapshot for telemetry/oom_report.json."""
        return {
            "active_slots": len(self._active),
            "prefilling_slots": len(self._prefilling),
            "max_seqs": self.max_seqs,
            "pages_in_use": self._pages_in_use,
            "free_pages": len(self.free_pages),
            "evictable_pages": len(self._evictable),
            "num_pages": self.num_pages,
            "page_size": self.page_size,
            "pool_bytes": self.pool_bytes(),
            "inflight_block": self._inflight is not None,
            "stats": dict(self.stats),
        }

    def clear_prefix_cache(self):
        """Drop all cached (refcount-0) prefix pages and their index. In-use
        pages are untouched — they free normally on retire (their index
        entries are already gone, so they cannot be matched again)."""
        for pid in list(self._evictable):
            self.free_pages.append(pid)
        self._evictable.clear()
        self._prefix_index.clear()
        self._page_hash.clear()

    # ---- prefix-cache page accounting -------------------------------------
    def _available_pages(self):
        return len(self.free_pages) + len(self._evictable)

    def _alloc_pages(self, n):
        """Take n pages: free list first, then LRU-evict cached ones."""
        out = []
        for _ in range(n):
            if self.free_pages:
                out.append(self.free_pages.pop())
                continue
            pid, _ = self._evictable.popitem(last=False)  # LRU
            key = self._page_hash.pop(pid)
            self._prefix_index.pop(key, None)
            self.stats["prefix_evictions"] += 1
            out.append(pid)
        return out

    def _ref_pages(self, pages):
        for p in pages:
            n = self._page_refs.get(p, 0)
            if n == 0:
                self._pages_in_use += 1
            self._page_refs[p] = n + 1
            self._evictable.pop(p, None)

    def _unref_pages(self, pages):
        for p in pages:
            self._page_refs[p] -= 1
            if self._page_refs[p] == 0:
                del self._page_refs[p]
                self._pages_in_use -= 1
                if p in self._page_hash:  # cached: keep KV, evict lazily
                    self._evictable[p] = None
                else:
                    self.free_pages.append(p)

    def pages_in_use(self):
        """Referenced (in-flight) pages, maintained O(1) at every ref/unref
        transition — the admit loop's pressure signal and the router's load
        input. Equals ``num_pages - 1 - free - evictable`` (asserted in
        tests)."""
        return self._pages_in_use

    def _page_digests(self, prompt, n_pages):
        """Chained per-page digests for the first ``n_pages`` full pages:
        digest[j] identifies prompt[:(j+1)*bs] but costs O(bs) to extend,
        so the whole chain is O(prompt bytes) — the index/probe key that
        replaced the old quadratic full-prefix re-hash."""
        bs = self.page_size
        out, h = [], b""
        for j in range(n_pages):
            h = hashlib.blake2b(prompt[j * bs:(j + 1) * bs].tobytes(),
                                key=h, digest_size=16).digest()
            out.append(h)
        return out

    def _match_prefix(self, prompt, true_len):
        """Longest run of indexed full pages, capped so >=1 suffix token
        remains to prefill (its logits produce the first sampled token).
        Returns (n, shared pages, the full digest chain — reused by
        _index_prompt_pages so each admission hashes the prompt once)."""
        bs = self.page_size
        p_max = (true_len - 1) // bs
        digests = self._page_digests(prompt, true_len // bs)
        shared = []
        for j in range(p_max):
            pid = self._prefix_index.get(digests[j])
            if pid is None:
                break
            shared.append(pid)
        return len(shared), shared, digests

    def prefix_match_pages(self, prompt):
        """How many full prompt pages this engine could serve from its
        prefix cache right now (read-only: no refcounts taken, no state
        touched). The router's affinity signal — O(prompt bytes) digest
        chain + dict probes only, safe to call from the frontend's submit
        thread while the dispatcher runs."""
        if not self.enable_prefix_cache:
            return 0
        p = np.asarray(prompt, np.int32).reshape(-1)
        n, _, _ = self._match_prefix(p, len(p))
        return n

    def _index_prompt_pages(self, true_len, pages, start, digests):
        """Register this request's full prompt pages (from page `start` on;
        earlier ones were matched, hence already indexed). ``digests`` is
        the chain _match_prefix computed at admission."""
        bs = self.page_size
        for j in range(start, len(pages)):
            if (j + 1) * bs > true_len:
                break
            key = digests[j]
            if key not in self._prefix_index:  # first writer wins
                self._prefix_index[key] = pages[j]
                self._page_hash[pages[j]] = key

    # ---- the KV handoff plane's jitted pieces (ISSUE 16) ------------------
    # export_pages gathers a request's pages dense, adopt_request scatters
    # them into another engine's pool (_insert below). Compiled by a
    # handoff, never by warm-up; no serving dispatch uses either.
    def _gather_prefix(self, n_pages):
        """pools + page ids [n_pages] -> dense KV [L, n*bs, Hkv, D]: what
        export_pages puts in a handoff bundle."""
        fn = self._gather_fns.get(n_pages)
        if fn is not None:
            return fn
        bs = self.page_size

        def read(pool, page_ids):
            # float pools only: an int8 pool raises here and export_pages'
            # caller degrades to blended serving
            arr = pool[:, page_ids]
            # [Hkv, n, bs, D] -> [n*bs, Hkv, D]
            arr = jnp.transpose(arr, (1, 2, 0, 3))
            return arr.reshape(n_pages * bs, arr.shape[2], arr.shape[3])

        def gather(pools, page_ids):
            ks = jnp.stack([read(kp, page_ids) for kp, _ in pools])
            vs = jnp.stack([read(vp, page_ids) for _, vp in pools])
            return ks, vs

        fn = self._gather_fns[n_pages] = _compilemem.ledgered_jit(
            gather, key=f"serve.gather[p{n_pages}]")
        _compilemem.ledger.note_cache_size("serve.gather",
                                           len(self._gather_fns))
        return fn

    @staticmethod
    def _pages_for_bucket(bucket, bs):
        return -(-bucket // bs)  # ceil: a bucket smaller than a page still needs one

    def _insert(self, bucket):
        """Scatter a handoff bundle's dense KV (`bucket` tokens, what
        _gather_prefix exported) into the pool pages adopt_request
        reserved. The bucket is padded up to a whole number of pages (the
        pad region is masked out by `lengths` everywhere)."""
        fn = self._insert_fns.get(bucket)
        if fn is not None:
            return fn
        bs = self.page_size
        npg = self._pages_for_bucket(bucket, bs)
        pad = npg * bs - bucket

        from ..ops.paged_attention import is_quantized

        def write_page(pool, pid, chunk):
            if is_quantized(pool):
                from jax.experimental.pallas.ops.tpu.paged_attention import (
                    quantization_utils as qu,
                )

                qt = qu.quantize_to_int8(chunk.astype(jnp.float32))
                return type(pool)(
                    weight=pool.weight.at[:, pid].set(qt.weight),
                    scales=pool.scales.at[:, pid].set(qt.scales),
                )
            return pool.at[:, pid].set(chunk.astype(pool.dtype))

        def insert(pools, ks, vs, page_ids):
            if pad:
                ks = jnp.pad(ks, ((0, 0), (0, pad), (0, 0), (0, 0)))
                vs = jnp.pad(vs, ((0, 0), (0, pad), (0, 0), (0, 0)))
            out = []
            for l, (kp, vp) in enumerate(pools):
                for j in range(npg):
                    chunk_k = jnp.swapaxes(ks[l, j * bs:(j + 1) * bs], 0, 1)
                    chunk_v = jnp.swapaxes(vs[l, j * bs:(j + 1) * bs], 0, 1)
                    kp = write_page(kp, page_ids[j], chunk_k)
                    vp = write_page(vp, page_ids[j], chunk_v)
                out.append((kp, vp))
            return tuple(out)

        # donate the pool: the engine discards the pre-insert buffers
        # immediately, and without donation XLA copies the whole pool
        fn = self._insert_fns[bucket] = _compilemem.ledgered_jit(
            insert, key=f"serve.insert[b{bucket}]", donate_argnums=(0,))
        _compilemem.ledger.note_cache_size("serve.insert",
                                           len(self._insert_fns))
        return fn

    # ---- dispatch locking -------------------------------------------------
    @contextmanager
    def _locked_dispatch(self, *keys):
        """Guard a jitted section. Warm program keys take only this
        engine's execution lock; any cold key additionally takes the
        process-wide compile lock for the duration (first call = trace).
        Keys are marked warm only after the section SUCCEEDS, so a
        retried transient failure recompiles under the lock again.
        ``_last_dispatch_cold`` records whether THIS section traced — the
        serving-goodput split attributes cold sections to 'compile'
        instead of decode."""
        cold = [k for k in keys if k not in self._warm]
        self._last_dispatch_cold = bool(cold)
        try:
            if not cold:
                with self.dispatch_lock:
                    chaos.site("obs.oom")
                    yield
                return
            with _COMPILE_LOCK, self.dispatch_lock:
                chaos.site("obs.oom")
                yield
            self._warm.update(cold)
        except Exception as e:
            # OOM-forensics seam (ISSUE 8): every engine dispatch — mixed
            # step, decode block, handoff insert — funnels through
            # here, so one interception covers them all. The report
            # commits (ledger + HBM budget + active slots/pages) before
            # the exception continues into the per-request isolation /
            # replica-death machinery.
            _compilemem.maybe_oom_report(
                e, program=str(keys[0]) if keys else None)
            raise

    def _xprof_annotation(self, req):
        """Host-side profiler annotation carrying the request's trace_id
        (``rtrace:<id>``): xprof's trace viewer shows it on the host
        timeline aligned with the device ops this dispatch enqueued — the
        join key between request traces and device profiles. Per-request
        program metadata is impossible (programs are compiled once and
        shared across requests), so the correlation is by host
        timeline, not op name. No-op without a trace."""
        if req.trace is None:
            return nullcontext()
        try:
            return jax.profiler.TraceAnnotation(
                f"rtrace:{req.trace.ctx.trace_id}")
        except Exception:
            return nullcontext()

    def _captured_state(self):
        """The version-checked raw_state_dict capture shared by admission
        and decode — keeps the O(n_params) tree walk off the latency-
        critical loop. Version read BEFORE the capture: a mutation landing
        in between tags fresh state with a stale version, which merely
        forces an extra refresh next time — never a stale serve.

        The refresh happens under the COMPILE lock: a sibling replica
        tracing the shared model temporarily rebinds its state through the
        framework's thread-oblivious Tensor plumbing, and a concurrent
        raw_state_dict() walk would capture those tracers (then feed them
        to a compiled program — the exact leak the old process-wide
        dispatch lock hid). Cache hits stay lock-free: a cached capture
        was taken outside any trace window and holds real arrays."""
        ver = _core.tensor_mutation_version()
        cache = self._decode_state_cache
        if cache is not None and cache[0] == ver:
            return cache[1]
        with _COMPILE_LOCK:
            state = self.model.raw_state_dict()
        self._decode_state_cache = (ver, state)
        return state

    # ---- jitted pieces ----------------------------------------------------
    # Per-row length CAPS (ISSUE 6): the block size is chosen from the
    # LARGEST remaining token budget in the batch, so rows with smaller
    # budgets ride past their budget inside the block (their overshoot
    # tokens are discarded at emit). The cap — true_len + max_new - 1, the
    # last page-reserved position — is clamped INSIDE the program so an
    # overshooting row freezes its write position at its last reserved
    # slot instead of writing past its reservation. For every row within
    # budget the clamp is the identity, so outputs stay bit-identical to
    # the uncapped program. Without this, one short-budget co-tenant drags
    # the whole batch's block size down to its own remaining count (the
    # k-fragmentation that measured 2x extra dispatches under staggered
    # chunked-prefill admissions).
    def _decode(self, sampling):
        fn = self._decode_fns.get(sampling)
        if fn is not None:
            return fn
        model = self.model
        sampler = _row_sampler(*sampling)

        def decode(state, toks, pools, page_table, lengths, caps, keys):
            overrides = {k: Tensor(v, stop_gradient=True) for k, v in state.items()}
            lengths_e = jnp.minimum(lengths, caps)
            pkvs = self._paged_views(pools, page_table, lengths_e, caps > 0)
            logits, presents = model.functional_call(
                overrides, Tensor(toks),
                position_ids=Tensor(lengths_e[:, None].astype(jnp.int32)),
                past_key_values=pkvs, use_cache=True, training=False,
            )
            nxt = sampler(logits._data[:, -1], keys).astype(jnp.int32)
            return nxt, self._pools_of(presents)

        # donate the pools: a single-token decode must UPDATE the pool in
        # place, not copy it — without donation every step pays a full-pool
        # memcpy and doubles peak memory, against the engine's whole point
        fn = self._decode_fns[sampling] = _compilemem.ledgered_jit(
            decode, key=f"serve.decode[s{sampling}]", donate_argnums=(2,))
        _compilemem.ledger.note_cache_size("serve.decode",
                                           len(self._decode_fns))
        return fn

    def _append_counters(self, blk, counters):
        """A model's per-dispatch counters (`serving_counters()`: int32, one
        a name of `serving_counter_names`, valid in its forward's own trace
        and summed here over the dispatch's forwards) as extra rows under the
        [k, max_seqs] token block, so that they come to the host with the
        block's one read-back (`_process_block` takes them off again)."""
        n = counters.shape[0]
        rows = -(-n // self.max_seqs)
        flat = jnp.zeros((rows * self.max_seqs,), blk.dtype).at[:n].set(
            counters.astype(blk.dtype))
        return jnp.concatenate([blk, flat.reshape(rows, self.max_seqs)])

    def _decode_block_fn(self, sampling, k):
        """k decode steps fused into one dispatch: lax.scan over the
        single-step decode body, carrying (tokens, pools, lengths). Returns
        the [k, max_seqs] token block + the updated pools."""
        fn = self._decode_block_fns.get((sampling, k))
        if fn is not None:
            return fn
        model = self.model
        sampler = _row_sampler(*sampling)
        count = getattr(model, "serving_counters", None)

        def decode_block(state, toks, pools, page_table, lengths, caps, keys):
            overrides = {kk: Tensor(v, stop_gradient=True) for kk, v in state.items()}

            def body(carry, step_keys):
                toks_c, pools_c, lengths_c = carry[:3]
                # freeze an over-budget row at its last reserved position
                # (identity for rows within budget — see caps note above)
                lengths_e = jnp.minimum(lengths_c, caps)
                pkvs = self._paged_views(pools_c, page_table, lengths_e,
                                         caps > 0)
                logits, presents = model.functional_call(
                    overrides, Tensor(toks_c),
                    position_ids=Tensor(lengths_e[:, None].astype(jnp.int32)),
                    past_key_values=pkvs, use_cache=True, training=False,
                )
                nxt = sampler(logits._data[:, -1], step_keys).astype(jnp.int32)
                new_pools = self._pools_of(presents)
                out = (nxt[:, None], new_pools, lengths_e + 1)
                if count is not None:
                    out += (carry[3] + count(),)
                return out, nxt

            init = (toks, tuple(pools), lengths)
            if count is not None:
                init += (jnp.zeros((len(model.serving_counter_names),),
                                   jnp.int32),)
            carry, toks_block = jax.lax.scan(body, init, keys)
            if count is not None:
                toks_block = self._append_counters(toks_block, carry[3])
            return toks_block, carry[1]

        fn = self._decode_block_fns[(sampling, k)] = _compilemem.ledgered_jit(
            decode_block, key=f"serve.decode_block[k{k},s{sampling}]",
            donate_argnums=(2,))
        _compilemem.ledger.note_cache_size("serve.decode_block",
                                           len(self._decode_block_fns))
        return fn

    # ---- per-request LoRA programs (ISSUE 19) -----------------------------
    # An adapter is a low-rank update to the LM-HEAD projection:
    #
    #     logits = base_head(h) + scale * (h @ A) @ B
    #
    # with A [hidden, r] / B [r, vocab] float32. The lora program variants
    # run the model's trunk (model.serving_trunk()) through functional_call —
    # exactly the ops the base programs run — then apply the same-ops base
    # head plus the gathered per-row delta. The compile-time constants are
    # (sampling, rank, block k); adapter WEIGHTS are runtime operands
    # (decode: fixed-depth [_lora_slots+1, ...] stacks indexed per row,
    # slot 0 all-zeros), so hot-swapping adapters within a warmed
    # (rank, sampling) signature never recompiles. A batch with no
    # adapters at all never enters these programs: the base path stays
    # byte-for-byte the pre-LoRA engine.

    @staticmethod
    def _trunk_overrides(state, prefix):
        """Full-model raw state -> functional_call overrides of the trunk
        `model.serving_trunk()` names (its prefix stripped; the head weight
        stays behind for `model.serving_head`, which runs the SAME ops the
        model's own forward uses, so a zero-delta lora row samples the
        bit-identical token the base program would have)."""
        return {k[len(prefix):]: Tensor(v, stop_gradient=True)
                for k, v in state.items() if k.startswith(prefix)}

    def _lora_decode(self, sampling, rank):
        """Single-step batched multi-adapter decode: per-row indices
        gather each slot's A/B/scale from the fixed-depth stacks inside
        the program. Row 0 of the stacks is zeros — no-adapter co-tenants
        add an exact 0.0 delta and sample the base token bit-for-bit."""
        key2 = (sampling, rank)
        fn = self._lora_decode_fns.get(key2)
        if fn is not None:
            return fn
        model = self.model
        inner, prefix = model.serving_trunk()
        sampler = _row_sampler(*sampling)

        def decode(state, toks, pools, page_table, lengths, caps, keys,
                   a_stack, b_stack, scales, lora_idx):
            overrides = self._trunk_overrides(state, prefix)
            lengths_e = jnp.minimum(lengths, caps)
            pkvs = self._paged_views(pools, page_table, lengths_e, caps > 0)
            h, presents = inner.functional_call(
                overrides, Tensor(toks),
                position_ids=Tensor(lengths_e[:, None].astype(jnp.int32)),
                past_key_values=pkvs, use_cache=True, training=False,
            )
            hd = h._data                       # [max_seqs, 1, hidden]
            base = model.serving_head(hd, state)
            a_rows = a_stack[lora_idx]         # [max_seqs, hidden, r]
            b_rows = b_stack[lora_idx]         # [max_seqs, r, vocab]
            delta = jnp.einsum("bsh,bhr->bsr", hd.astype(jnp.float32),
                               a_rows)
            delta = jnp.einsum("bsr,brv->bsv", delta, b_rows)
            logits = base + delta * scales[lora_idx][:, None, None]
            nxt = sampler(logits[:, -1], keys).astype(jnp.int32)
            return nxt, self._pools_of(presents)

        fn = self._lora_decode_fns[key2] = _compilemem.ledgered_jit(
            decode, key=f"serve.lora_decode[r{rank},s{sampling}]",
            donate_argnums=(2,))
        _compilemem.ledger.note_cache_size("serve.lora_decode",
                                           len(self._lora_decode_fns))
        return fn

    def _lora_block_fn(self, sampling, rank, k):
        """k lora decode steps fused into one dispatch — _decode_block_fn
        with the adapter gather applied per scan step (the gathered rows
        are loop-invariant, hoisted once outside the scan)."""
        key3 = (sampling, rank, k)
        fn = self._lora_block_fns.get(key3)
        if fn is not None:
            return fn
        model = self.model
        inner, prefix = model.serving_trunk()
        sampler = _row_sampler(*sampling)

        def decode_block(state, toks, pools, page_table, lengths, caps,
                         keys, a_stack, b_stack, scales, lora_idx):
            overrides = self._trunk_overrides(state, prefix)
            a_rows = a_stack[lora_idx]
            b_rows = b_stack[lora_idx]
            s_rows = scales[lora_idx][:, None, None]

            def body(carry, step_keys):
                toks_c, pools_c, lengths_c = carry
                lengths_e = jnp.minimum(lengths_c, caps)
                pkvs = self._paged_views(pools_c, page_table, lengths_e,
                                         caps > 0)
                h, presents = inner.functional_call(
                    overrides, Tensor(toks_c),
                    position_ids=Tensor(
                        lengths_e[:, None].astype(jnp.int32)),
                    past_key_values=pkvs, use_cache=True, training=False,
                )
                hd = h._data
                base = model.serving_head(hd, state)
                delta = jnp.einsum("bsh,bhr->bsr",
                                   hd.astype(jnp.float32), a_rows)
                delta = jnp.einsum("bsr,brv->bsv", delta, b_rows)
                logits = base + delta * s_rows
                nxt = sampler(logits[:, -1], step_keys).astype(jnp.int32)
                new_pools = self._pools_of(presents)
                return (nxt[:, None], new_pools, lengths_e + 1), nxt

            (_, pools_out, _), toks_block = jax.lax.scan(
                body, (toks, tuple(pools), lengths), keys)
            return toks_block, pools_out

        fn = self._lora_block_fns[key3] = _compilemem.ledgered_jit(
            decode_block,
            key=f"serve.lora_decode_block[r{rank},k{k},s{sampling}]",
            donate_argnums=(2,))
        _compilemem.ledger.note_cache_size("serve.lora_decode_block",
                                           len(self._lora_block_fns))
        return fn

    # ---- mixed programs (ISSUE 20) ----------------------------------------
    # ONE program per (sampling, kv-dtype[, lora-rank]). The packed pass
    # runs every prompt chunk and every decode feed token in a single
    # [T]-token forward through the ragged paged cache (prompt length is a
    # RUNTIME operand — cu_q_lens — not a compile-time bucket), samples
    # each participant's boundary token, then scans the remaining k-1
    # fixed decode steps with the decode block's body. Mid-prefill rows
    # are excluded from the scan by construction: their caps are 0 (write
    # position frozen at 0) and their scan_table row is all-zeros, so
    # their scan writes land in the scratch page.

    def _ragged_fn(self, sampling):
        fn = self._ragged_fns.get(sampling)
        if fn is not None:
            return fn
        model = self.model
        inner, prefix = model.serving_trunk()
        sampler = _row_sampler(*sampling)
        count = getattr(model, "serving_counters", None)
        # a model whose upper layers cache nothing (they read a lower
        # layer's pool) names them its TAIL: the trunk runs on the packed
        # tokens, the tail on the span ends alone, then the head
        tail = getattr(model, "serving_tail", None)
        T = self._ragged_tokens
        k = self.decode_block

        def ragged_step(state, tok_block, cu, row_of, token_pos, valid,
                        use_last, last, pools, page_table, scan_table,
                        lengths, caps, keys):
            overrides = {kk: Tensor(v, stop_gradient=True)
                         for kk, v in state.items()}
            inner_ov = self._trunk_overrides(state, prefix)
            q_lens = cu[1:] - cu[:-1]
            # decode rows chained off an in-flight block feed on its device
            # `last` tokens; each row's feed token sits at its span start.
            # Rows with q_len == 0 alias position min(cu, T-1) — they write
            # back the value already there, so duplicates are harmless.
            first_idx = jnp.minimum(cu[:-1], T - 1)
            upd = jnp.where(use_last[:, 0], last[:, 0], tok_block[first_idx])
            toks_in = tok_block.at[first_idx].set(upd)
            kv_lens = lengths + q_lens  # POST-write totals (ragged contract)
            rcaches = self._ragged_views(pools, page_table, kv_lens, cu,
                                         row_of, token_pos, valid)
            h, presents = inner.functional_call(
                inner_ov, Tensor(toks_in[None]),
                position_ids=Tensor(token_pos[None].astype(jnp.int32)),
                past_key_values=rcaches, use_cache=True, training=False,
            )
            # each participant samples from its LAST packed token (span end)
            b_idx = jnp.clip(cu[1:] - 1, 0, T - 1)
            if tail is None:
                h_b = h._data[0, b_idx]                     # [max_seqs, H]
            else:
                h_b = tail(inner_ov, h, b_idx, presents)
            base = model.serving_head(h_b, state)   # [max_seqs, V]
            tok0 = sampler(base, keys[0]).astype(jnp.int32)
            pools1 = self._pools_of(presents)
            init = (tok0[:, None], pools1, kv_lens)
            if count is not None:
                init += (count(),)  # the packed pass's; the scan adds its own

            def body(carry, step_keys):
                toks_c, pools_c, lengths_c = carry[:3]
                lengths_e = jnp.minimum(lengths_c, caps)
                pkvs = self._paged_views(pools_c, scan_table, lengths_e,
                                         caps > 0)
                logits, presents2 = model.functional_call(
                    overrides, Tensor(toks_c),
                    position_ids=Tensor(lengths_e[:, None].astype(jnp.int32)),
                    past_key_values=pkvs, use_cache=True, training=False,
                )
                nxt = sampler(logits._data[:, -1], step_keys).astype(jnp.int32)
                new_pools = self._pools_of(presents2)
                out = (nxt[:, None], new_pools, lengths_e + 1)
                if count is not None:
                    out += (carry[3] + count(),)
                return out, nxt

            carry, toks_tail = jax.lax.scan(body, init, keys[1:])
            blk = jnp.concatenate([tok0[None], toks_tail], axis=0)
            if count is not None:
                blk = self._append_counters(blk, carry[3])
            return blk, carry[1]

        fn = self._ragged_fns[sampling] = _compilemem.ledgered_jit(
            ragged_step, key=f"serve.ragged[k{k},s{sampling}]",
            donate_argnums=(8,))
        _compilemem.ledger.note_cache_size("serve.ragged",
                                           len(self._ragged_fns))
        return fn

    def _lora_ragged_fn(self, sampling, rank):
        """_ragged_fn with the fixed-depth adapter-stack gather on every
        head projection (packed boundary rows AND scan steps) — slot 0 of
        the stacks is zeros, so no-adapter rows add an exact +0.0 delta."""
        key2 = (sampling, rank)
        fn = self._lora_ragged_fns.get(key2)
        if fn is not None:
            return fn
        model = self.model
        inner, prefix = model.serving_trunk()
        sampler = _row_sampler(*sampling)
        T = self._ragged_tokens
        k = self.decode_block

        def ragged_step(state, tok_block, cu, row_of, token_pos, valid,
                        use_last, last, pools, page_table, scan_table,
                        lengths, caps, keys, a_stack, b_stack, scales,
                        lora_idx):
            inner_ov = self._trunk_overrides(state, prefix)
            a_rows = a_stack[lora_idx]
            b_rows = b_stack[lora_idx]
            s_rows = scales[lora_idx]
            q_lens = cu[1:] - cu[:-1]
            first_idx = jnp.minimum(cu[:-1], T - 1)
            upd = jnp.where(use_last[:, 0], last[:, 0], tok_block[first_idx])
            toks_in = tok_block.at[first_idx].set(upd)
            kv_lens = lengths + q_lens
            rcaches = self._ragged_views(pools, page_table, kv_lens, cu,
                                         row_of, token_pos, valid)
            h, presents = inner.functional_call(
                inner_ov, Tensor(toks_in[None]),
                position_ids=Tensor(token_pos[None].astype(jnp.int32)),
                past_key_values=rcaches, use_cache=True, training=False,
            )
            b_idx = jnp.clip(cu[1:] - 1, 0, T - 1)
            h_b = h._data[0, b_idx]
            base = model.serving_head(h_b, state)
            delta = jnp.einsum("bh,bhr->br", h_b.astype(jnp.float32), a_rows)
            delta = jnp.einsum("br,brv->bv", delta, b_rows)
            tok0 = sampler(base + delta * s_rows[:, None],
                           keys[0]).astype(jnp.int32)
            pools1 = self._pools_of(presents)
            s3 = s_rows[:, None, None]

            def body(carry, step_keys):
                toks_c, pools_c, lengths_c = carry
                lengths_e = jnp.minimum(lengths_c, caps)
                pkvs = self._paged_views(pools_c, scan_table, lengths_e,
                                         caps > 0)
                h2, presents2 = inner.functional_call(
                    inner_ov, Tensor(toks_c),
                    position_ids=Tensor(lengths_e[:, None].astype(jnp.int32)),
                    past_key_values=pkvs, use_cache=True, training=False,
                )
                hd = h2._data
                base2 = model.serving_head(hd, state)
                d2 = jnp.einsum("bsh,bhr->bsr", hd.astype(jnp.float32),
                                a_rows)
                d2 = jnp.einsum("bsr,brv->bsv", d2, b_rows)
                logits = base2 + d2 * s3
                nxt = sampler(logits[:, -1], step_keys).astype(jnp.int32)
                new_pools = self._pools_of(presents2)
                return (nxt[:, None], new_pools, lengths_e + 1), nxt

            (_, pools_out, _), toks_tail = jax.lax.scan(
                body, (tok0[:, None], pools1, kv_lens), keys[1:])
            blk = jnp.concatenate([tok0[None], toks_tail], axis=0)
            return blk, pools_out

        fn = self._lora_ragged_fns[key2] = _compilemem.ledgered_jit(
            ragged_step, key=f"serve.lora_ragged[r{rank},k{k},s{sampling}]",
            donate_argnums=(8,))
        _compilemem.ledger.note_cache_size("serve.lora_ragged",
                                           len(self._lora_ragged_fns))
        return fn

    # ---- LoRA weight residency --------------------------------------------
    def _lora_dev(self, adapter):
        """Host A/B -> device arrays, digest-keyed LRU (the hot working
        set transfers once; re-registration under a new digest is a new
        entry, so stale weights can never serve)."""
        ent = self._lora_device.get(adapter.digest)
        if ent is None:
            ent = (jnp.asarray(adapter.a), jnp.asarray(adapter.b))
            self._lora_device[adapter.digest] = ent
            while len(self._lora_device) > 32:
                self._lora_device.popitem(last=False)
        else:
            self._lora_device.move_to_end(adapter.digest)
        return ent

    def _lora_stack(self, rank, adapters):
        """(a_stack, b_stack, scales, digest->index) for a digest-sorted
        working set. Depth is FIXED at ``_lora_slots + 1`` (slot 0 =
        zeros for no-adapter rows; tail slots zero-padded) so the decode
        signature never varies with the working set — the zero-warm-
        recompile contract. Keyed by (rank, digests), LRU-bounded."""
        digs = tuple(ad.digest for ad in adapters)
        cached = self._lora_stack_cache.get((rank, digs))
        if cached is None:
            hidden, vocab = self._lora_dims
            za = jnp.zeros((hidden, rank), jnp.float32)
            zb = jnp.zeros((rank, vocab), jnp.float32)
            a_list, b_list, s_list = [za], [zb], [0.0]
            for ad in adapters:
                a_dev, b_dev = self._lora_dev(ad)
                a_list.append(a_dev)
                b_list.append(b_dev)
                s_list.append(float(ad.scale))
            while len(a_list) < self._lora_slots + 1:
                a_list.append(za)
                b_list.append(zb)
                s_list.append(0.0)
            cached = (jnp.stack(a_list), jnp.stack(b_list),
                      jnp.asarray(s_list, jnp.float32))
            self._lora_stack_cache[(rank, digs)] = cached
            while len(self._lora_stack_cache) > 8:
                self._lora_stack_cache.popitem(last=False)
        else:
            self._lora_stack_cache.move_to_end((rank, digs))
        return cached + ({d: i + 1 for i, d in enumerate(digs)},)

    def _lora_reject(self, ad):
        """Why this adapter can never run on this engine (None = it can):
        admission fails the request alone instead of deferring forever."""
        hidden, vocab = self._lora_dims
        why = self._cache_spec.refuses("lora")
        if why:
            return ValueError(f"the LoRA planes {why}")
        if not hasattr(self.model, "serving_trunk") or hidden is None \
                or vocab is None:
            return ValueError(
                "LoRA adapters need a model with the serving protocol "
                "(serving_trunk/serving_head + hidden_size/vocab_size "
                "config)")
        if ad.a.shape[0] != hidden or ad.b.shape[1] != vocab:
            return ValueError(
                f"adapter {ad.name!r} shapes {ad.a.shape}/{ad.b.shape} "
                f"do not match model hidden={hidden} vocab={vocab}")
        return None

    def warmup(self, prompt_lens=None, do_sample=False, temperature=1.0,
               top_k=0, top_p=1.0, buckets=None, sampling=None,
               lora_ranks=()):
        """Compile every program serve() can hit BEFORE latency-sensitive
        serving (reference: AnalysisPredictor warmup / TRT engine
        build-ahead): one dummy serve per sampling configuration, which
        dispatches the mixed step and the decode block — the whole program
        set of steady-state traffic. Without this both compile inside the
        serving loop, seconds against the milliseconds of a dispatch.

        ``prompt_lens`` / ``buckets`` (an alias: the AOT-precompile
        vocabulary the serving frontend uses at replica start) name the
        traffic a caller expects; one of them is required, and neither
        selects a program, prompt length being a runtime operand of the
        mixed step. ``sampling`` precompiles for a LIST of sampling
        configs in one call — each entry is a ``(do_sample, temperature,
        top_k, top_p)`` tuple (or a single tuple) — since the sampler is a
        compile-time constant of both programs. Wall time lands in the
        ``serve.compile_warmup_s`` histogram.

        ``lora_ranks`` (ISSUE 19) additionally compiles the per-request
        LoRA program pair for each adapter rank by serving a zero-weight
        adapter of that rank (adapter weights are runtime operands, so
        warming any adapter warms them all for the rank).

        The KV handoff plane's gather and insert programs are compiled by a
        handoff (export_pages / adopt_request), never here."""
        if buckets is not None:
            prompt_lens = buckets
        if prompt_lens is None:
            raise ValueError("warmup() needs prompt_lens= or buckets=")
        if sampling is None:
            configs = [(do_sample, temperature, top_k, top_p)]
        elif sampling and not isinstance(sampling[0], (tuple, list)):
            configs = [tuple(sampling)]
        else:
            configs = [tuple(s) for s in sampling]
        # the set-up log's `engine.warmup` (observability/tracing.py), each
        # compile a child of the serve that made it. Synced by what it
        # does: every dummy serve reads its tokens back
        phase = _trace.setup_phase("engine.warmup", synced=True)
        compiled0 = _compilemem.ledger.counts()["events"]
        try:
            # ledger trigger scope (ISSUE 8): compiles inside warmup are
            # deliberate AOT work, not cold-path stalls — /compilez and
            # the bench contract separate them by this label
            with phase, _compilemem.ledger.trigger("warmup"):
                for cfg in configs:
                    self._warmup_serve(*cfg)
                for rank in lora_ranks:
                    for cfg in configs:
                        self._warmup_serve(*cfg, lora_rank=int(rank))
                self._publish_scopes()
                phase.counts["programs"] = (
                    _compilemem.ledger.counts()["events"] - compiled0)
        finally:
            _M_WARMUP.observe((phase.t1_ns - phase.t0_ns) / 1e9)

    def _publish_scopes(self):
        """For a model that names `serving_scopes` (its `jax.named_scope`s
        inside the step programs): which scope each instruction of the two
        warmed step programs lies under, into `tracing.program_scopes`, so
        that a device trace's op events can be attributed to them. One
        re-lowering a program through the compile cache, in warm-up."""
        scopes = getattr(self.model, "serving_scopes", None)
        if not scopes:
            return
        with _trace.setup_phase("engine.warmup.scopes", programs=0,
                                scopes=0) as phase:
            for key in list(_compilemem.memory.programs()):
                if key.startswith(("serve.ragged[", "serve.decode_block[")):
                    try:
                        text = _compilemem.memory.compiled(key).as_text()
                    except KeyError:
                        # an earlier engine's program, since collected
                        continue
                    table = _trace.note_program_scopes(key, text, scopes)
                    phase.counts["programs"] += 1
                    phase.counts["scopes"] += len(table)

    def _warmup_serve(self, do_sample, temperature, top_k, top_p,
                      lora_rank=None):
        """One dummy serve of a one-token prompt: max_new = decode_block + 1
        touches the mixed program (the graduation step) AND the decode
        block (the following step); a sampled configuration builds the key
        program inside those dispatches. With ``lora_rank`` the request
        carries a zero-weight adapter of that rank (delta == 0, as
        harmless as the base serve), which compiles the rank's lora
        pair."""
        kw = dict(do_sample=do_sample, temperature=temperature,
                  top_k=top_k, top_p=top_p)
        if lora_rank is not None:
            from ..serving.adapters import LoRAAdapter

            hidden, vocab = self._lora_dims
            kw["adapters"] = LoRAAdapter(
                f"warmup-r{lora_rank}",
                np.zeros((hidden, lora_rank), np.float32),
                np.zeros((lora_rank, vocab), np.float32))
        stats_before = dict(self.stats)  # warmup must not pollute diagnostics
        # bypass the prefix cache: the all-ones dummy prompt must leave no
        # junk page indexed
        pfx, self.enable_prefix_cache = self.enable_prefix_cache, False
        try:
            fit = min(self.max_len - 1,
                      self._available_pages() * self.page_size - 1)
            n = max(min(self.decode_block + 1, fit), 1)
            with _trace.setup_phase("engine.warmup.serve"):
                self.serve([np.ones(1, np.int32)], max_new_tokens=n, **kw)
        finally:
            self.enable_prefix_cache = pfx  # lint: shared-mutation-without-lock-ok (engine fields are dispatcher-owned — single-threaded by contract)
            self.stats = stats_before  # lint: shared-mutation-without-lock-ok (same dispatcher-owned contract)

    # ---- scheduler --------------------------------------------------------
    def pool_bytes(self):
        import jax

        return sum(l.size * l.dtype.itemsize
                   for l in jax.tree_util.tree_leaves(self.pools))

    #: bounded retry for the decode dispatch: a transient dispatch failure
    #: (injected outage, flaky transport to a remote backend) retries the
    #: whole batch step; deterministic compile/shape errors are not
    #: ConnectionErrors and still raise immediately.
    retry_policy = RetryPolicy(attempts=3, base_delay=0.05)


    # ---- online request lifecycle -----------------------------------------
    # The serving control plane (paddle_tpu/serving) drives the engine with
    # these three hooks from a per-replica dispatcher thread:
    #
    #   try_admit_one(req)  non-blocking admission of ONE EngineRequest
    #   step()              one fused decode dispatch; returns finished reqs
    #   drain()             finish everything admitted, admit nothing
    #
    # serve() below is rebuilt ON TOP of the same hooks, so the batch path
    # and the online path cannot drift. The engine is single-threaded by
    # contract: all three hooks must be called from one thread (the
    # dispatcher); the only cross-thread writes it tolerates are the
    # EngineRequest.cancelled flags, honored at block boundaries.

    def idle(self):
        return (not self._active and not self._prefilling
                and self._inflight is None and not self._pending_retired)

    def active_count(self):
        # mid-chunked-prefill requests occupy slots too — the router's
        # load signal must see them
        return len(self._active) + len(self._prefilling)

    def has_free_slot(self):
        return bool(self.free_slots)

    def active_prefills(self):
        """Mid-chunked-prefill slot count — the brownout ladder's
        ``shed_prefill_depth`` rung caps this before shedding requests,
        and the frontend's role-aware pressure split reads it."""
        return len(self._prefilling)

    # ---- disaggregated prefill/decode handoff hooks (ISSUE 16) ------------
    # A prefill-role replica produces a request's first tokens, then the
    # frontend exports its KV pages, publishes a handoff bundle
    # (serving/handoff.py), detaches the request WITHOUT finishing its
    # handle, and a decode-role replica adopts the pages into its own pool
    # and continues bit-identically. All three hooks run on the owning
    # dispatcher thread (the engine's single-threaded contract).

    def _refuse(self, plane, what):
        """A plane written for K and V pages, asked of a cache whose spec
        refuses it (latent rows, state slots), says so by name: none may
        silently fall back."""
        why = self._cache_spec.refuses(plane)
        if why:
            raise ValueError(f"{what} {why}")

    def _paged_views(self, pools, page_table, lengths, live):
        """Each layer's pool as its own spec's decode view."""
        return [s.paged(pool, page_table, lengths, live)
                for s, pool in zip(self._layer_specs, pools)]

    def _ragged_views(self, pools, page_table, kv_lens, cu, row_of,
                      token_pos, valid):
        """Each layer's pool as its own spec's packed-stream view."""
        return [s.ragged(pool, page_table, kv_lens, cu, row_of, token_pos,
                         valid) for s, pool in zip(self._layer_specs, pools)]

    def _pools_of(self, presents):
        """The pools back out of the entries a forward returned."""
        return tuple(s.pool_of(p)
                     for s, p in zip(self._layer_specs, presents))

    def _settle_inflight(self):
        """Read back the in-flight decode block NOW (instead of at the next
        step()) so every active request's emitted tokens equal its
        dispatched tokens — the consistency an exported bundle needs.
        Requests that retire during the readback are queued for the next
        step() to return, so the frontend still sees them finish."""
        rec = self._inflight
        if rec is not None:
            self._inflight = None
            self._pending_retired.extend(self._process_block(rec))

    def export_pages(self, slot):
        """Gather ``slot``'s KV pages to the host for a handoff bundle:
        ``{"n_pages", "ks", "vs"}`` with dense ``[L, n*bs, Hkv, D]``
        arrays (the prefix-cache gather, reused — float pools only; int8
        export raises and the caller degrades to blended). Returns None
        when the request finished while the in-flight block settled —
        nothing left to hand off. Prefill-side only: the host sync here is
        deliberate and NOT part of any decode critical section."""
        self._refuse("handoff", "export_pages (the KV handoff plane)")
        self._settle_inflight()
        req = self._active.get(slot)
        if req is None or req.finished:
            return None
        n = len(req.pages)
        ks, vs = self._gather_prefix(n)(
            tuple(self.pools), jnp.asarray(req.pages, jnp.int32))
        return {"n_pages": n, "ks": np.asarray(ks), "vs": np.asarray(vs)}

    def detach_request(self, slot):
        """Release ``slot`` WITHOUT finishing the request's handle: the
        request now lives in its published bundle and the adopting decode
        replica continues it. Frees the slot and pages exactly like
        _retire but leaves the EngineRequest unfinished (tokens,
        dispatch count, and key stream intact for the adopter). Call only
        after export_pages() in the same dispatcher turn — no step() may
        run in between, or the detached bundle goes stale."""
        req = self._active.pop(slot)
        self._unref_pages(req.pages)
        self.free_slots.append(slot)
        self.page_table[slot] = 0
        self.lengths[slot] = 0
        req.pages = []
        req.slot = None
        self._slot_adapter.pop(slot, None)
        if not self._active and not self._prefilling:
            self._active_sampling = None
            self._active_lora_rank = None
        return req

    def adopt_request(self, req, payloads):
        """Admission twin for a handed-off request: scatter its exported
        page payloads into this pool and register it mid-decode. ``req``
        already carries the bundle's validated continuation state (tokens,
        n_dispatched, last_token). Returns "admitted" / "deferred" /
        "failed" with try_admit_one's exact semantics. Restores the decode
        invariant ``lengths[slot] = len(prompt) + n_dispatched - 1`` so
        the next decode block's positions — and with the replayed key
        stream, its tokens — are bit-identical to never having moved.
        Adopted pages are private (never prefix-indexed): their digests
        were validated against the bundle, not against this pool's index."""
        self._refuse("handoff", "adopt_request (the KV handoff plane)")
        if not self.free_slots:
            return "deferred"
        if (self._active or self._prefilling) \
                and self._active_sampling != req.sampling:
            return "deferred"
        n = int(payloads["n_pages"])
        if n > self.pages_per_seq:
            self._fail_request(req, ValueError(
                f"request {req.rid}: handoff bundle spans {n} pages, "
                f"page table holds {self.pages_per_seq}"))
            return "failed"
        if n > self._available_pages():
            if not self._active and not self._prefilling:
                self._fail_request(req, RuntimeError(
                    f"request {req.rid}: handoff bundle needs {n} pages, "
                    f"idle pool has {self._available_pages()}"))
                return "failed"
            self.stats["deferred_admissions"] += 1
            return "deferred"
        slot = self.free_slots.pop()
        pages = self._alloc_pages(n)
        self._ref_pages(pages)
        self.stats["peak_pages"] = max(self.stats["peak_pages"],
                                       self._pages_in_use)
        bucket = n * self.page_size
        try:
            with self._locked_dispatch(("insert", bucket)), \
                    _trace.span("serve.adopt"), self._xprof_annotation(req):
                chaos.site("serve.prefill")
                self.pools = list(self._insert(bucket)(
                    tuple(self.pools), jnp.asarray(payloads["ks"]),
                    jnp.asarray(payloads["vs"]),
                    jnp.asarray(pages, jnp.int32)))
        except Exception as e:  # fail THIS request alone, free everything
            self._unref_pages(pages)
            self.free_slots.append(slot)
            self._fail_request(req, e)
            return "failed"
        if req.sampling[0] and req.key_base is None:
            # same (seed, rid)-only stream root the prefill side used — an
            # 8-byte pull at adoption time, before any decode dispatch
            req.key_base = np.asarray(jax.random.fold_in(  # serve-readback-ok
                jax.random.PRNGKey(req.seed), req.rid))
        row = np.zeros(self.pages_per_seq, np.int32)
        row[:n] = pages
        self.page_table[slot] = row
        self.lengths[slot] = len(req.prompt) + req.n_dispatched - 1
        req.pages = pages
        req.slot = slot
        if req.t_admit is None:
            req.t_admit = time.monotonic()
        self._active[slot] = req
        self._active_sampling = req.sampling
        self._update_gauges()
        return "admitted"

    def _refresh_cache_guard(self, state):
        """Cached prefix KV is only valid under the weights it was computed
        with. Two-factor guard:
        - core.tensor_mutation_version: bumped by every set_value/load path
          AND the optimizer/train-step direct-rebind epilogues. A counter can
          never false-match when CPython recycles a freed array's address
          (the id()-only guard's failure mode, ADVICE r5 medium).
        - the id tuple: belt-and-braces for any future code that rebinds
          p._data without bumping — a rebind only slips through if EVERY new
          array also lands on its old address."""
        version = (_core.tensor_mutation_version(),
                   tuple(id(v) for v in state.values()))
        if version != self._cache_weights_version:
            if self._cache_weights_version is not None:
                self.clear_prefix_cache()
            self._cache_weights_version = version

    def _fail_request(self, req, exc):
        req.error = exc
        req.result = None
        req.finished = True
        req.t_done = time.monotonic()
        self.request_errors[req.rid] = exc
        # online mode: bounded map. serve() raises the bound to its batch
        # size for the duration — its docstring promises EVERY failed rid
        # an entry, and a >1024-request batch must not silently evict its
        # own early failures.
        while len(self.request_errors) > self._request_errors_bound:
            self.request_errors.pop(next(iter(self.request_errors)))
        self.stats["failed_requests"] += 1
        counters.bump("fault.serve.request_failed")

    def _retire(self, slot):
        req = self._active.pop(slot)
        req.result = np.asarray(req.tokens, np.int32)
        req.finished = True
        req.t_done = time.monotonic()
        self._unref_pages(req.pages)
        self.free_slots.append(slot)
        self.page_table[slot] = 0
        self.lengths[slot] = 0
        self._slot_adapter.pop(slot, None)
        if not self._active and not self._prefilling:
            self._active_sampling = None
            self._active_lora_rank = None
        return req

    def _release_prefill(self, slot):
        """Take ``slot`` out of mid-prefill and free everything it held
        (pages, slot, page-table row, adapter); returns its
        _PrefillState. Shared by abort and by the failure seams, so the
        release protocol cannot drift between them."""
        st = self._prefilling.pop(slot)
        self._unref_pages(st.pages)
        self.free_slots.append(slot)
        # admission installed the row up front: reset it, so no stale row
        # leaks to the slot's next tenant
        self.page_table[slot] = 0
        self.lengths[slot] = 0
        self._slot_adapter.pop(slot, None)
        if not self._active and not self._prefilling:
            self._active_sampling = None
            self._active_lora_rank = None
        return st

    def _fail_prefill(self, slot, exc):
        """A prefill dispatch failed: the prompt in ``slot`` fails ALONE —
        everything it held is freed, co-tenants are unaffected. Returns
        the failed request."""
        req = self._release_prefill(slot).req
        self._fail_request(req, exc)
        if req.trace is not None:
            req.trace.event("prefill_chunk_failed", error=req.error_message)
        return req

    def _abort_prefill(self, slot, timed_out=False):
        """Cancelled/timed-out mid-chunked-prefill: drop the remaining
        chunks and retire with the prompt-only partial result (no token
        was ever produced for it)."""
        req = self._release_prefill(slot).req
        req.result = np.asarray(req.tokens, np.int32)
        req.finished = True
        req.timed_out = timed_out
        req.t_done = time.monotonic()
        return req

    def _update_gauges(self):
        _M_OCCUPANCY.set(self.active_count() / self.max_seqs)
        free, evict = len(self.free_pages), len(self._evictable)
        _M_POOL_FREE.set(free)
        _M_POOL_EVICT.set(evict)
        _M_POOL_USED.set(self._pages_in_use)
        _M_POOL_FRAG.set(evict / (free + evict) if free + evict else 0.0)

    def try_admit_one(self, req):
        """Non-blocking admission of one :class:`EngineRequest`: a slot, a
        page reservation and the slot's page-table row — no device work,
        for any prompt length, adapter or kv dtype. The prompt streams
        into the pool inside step()'s mixed dispatches, beside everyone's
        decode rows, and its first token comes with the block that
        graduates it. Returns

        - ``"admitted"``  — holds a slot; drive it with step()
        - ``"failed"``    — terminally failed in isolation (``req.error``)
        - ``"deferred"``  — try again later: no free slot, the running
                            group's sampling differs, or the pool is busy

        The caller owns the queue: pop the request on every status except
        ``"deferred"``. A deferred request on an IDLE engine never happens —
        a request the idle pool still cannot fit fails as impossible instead
        (the degradation contract's "fail alone, never wedge the queue")."""
        if not self.free_slots:
            return "deferred"
        ad = req.adapter
        if self._active or self._prefilling:
            if self._active_sampling != req.sampling:
                # the sampler is a compile-time constant of the decode
                # program: only requests sharing a sampling tuple can
                # co-schedule (a mid-prefill request will join the decode
                # group too)
                return "deferred"
            if ad is not None:
                if self._active_lora_rank is None:
                    # base group running: its decode program has no adapter
                    # plane, and converting mid-group would move plain
                    # co-tenants off the byte-identical base path — wait
                    return "deferred"
                if ad.rank != self._active_lora_rank:
                    # rank is a compile-time constant of the lora programs
                    return "deferred"
                digs = {a.digest for a in self._slot_adapter.values()}
                if ad.digest not in digs and len(digs) >= self._lora_slots:
                    # stacked-weights working set full (PADDLE_LORA_SLOTS)
                    return "deferred"
        # past the deferral gates the request is popped by the caller on
        # every return below, so this counts each request exactly once —
        # on BOTH the batch serve() path and the frontend's online path
        _M_REQUESTS.inc()
        # request-scoped trace (ISSUE 7): the admission span nests under
        # the frontend's attempt span; every return below closes it
        adm = req.trace.child("admit") if req.trace is not None else None
        prompt = req.prompt
        true_len = len(prompt)
        if true_len + req.max_new_tokens > self.max_len:
            # invalid request — reject IT, not the whole batch
            self._fail_request(req, ValueError(
                f"request {req.rid}: len {true_len} + "
                f"{req.max_new_tokens} exceeds max_len={self.max_len}"))
            if adm is not None:
                adm.end("error", error=req.error_message)
            return "failed"
        if ad is not None:
            err = self._lora_reject(ad)
            if err is not None:
                # wrong-model/wrong-shape adapter can NEVER run here —
                # fail it alone instead of deferring forever
                self._fail_request(req, err)
                if adm is not None:
                    adm.end("error", error=req.error_message)
                return "failed"
        bs_ = self.page_size
        if self.enable_prefix_cache:
            # the version-checked capture, shared with the decode steps: the
            # O(n_params) tree walk stays off the TTFT-critical path
            self._refresh_cache_guard(self._captured_state())
            n_pre, shared, digests = self._match_prefix(prompt, true_len)
        else:
            n_pre, shared, digests = 0, [], None
        # prompt chunks land token-exact, so a hit of any width fits the
        # page-table row and the reservation is the request's true footprint
        total_need = -(-(true_len + req.max_new_tokens) // bs_)
        # hold the shared pages BEFORE the availability check: shared pages
        # sitting in _evictable would otherwise be double-counted as
        # allocatable, letting _alloc_pages run dry
        self._ref_pages(shared)
        if total_need - n_pre > self._available_pages():
            self._unref_pages(shared)
            if not self._active and not self._prefilling:
                # nothing running and it still can't admit: with the pool
                # otherwise idle that means it NEVER fits (needs more pages
                # than exist). Fail it alone, keep the queue draining.
                self._fail_request(req, RuntimeError(
                    f"request {req.rid} needs more pages than the pool holds "
                    f"({true_len}+{req.max_new_tokens} tokens vs "
                    f"{(self.num_pages - 1) * self.page_size} pool tokens)"))
                if adm is not None:
                    adm.end("error", error=req.error_message)
                return "failed"
            self.stats["deferred_admissions"] += 1
            if adm is not None:  # honest trace: each deferred probe shows
                adm.end("deferred", need_pages=total_need - n_pre)
            return "deferred"
        if self.enable_prefix_cache:
            # hit-rate denominator, counted once per ADMISSION (a deferred
            # request re-enters try_admit_one every decode block and must
            # not inflate it): every full prompt page that could have come
            # from cache
            _M_PREFIX_LOOKUP.inc((true_len - 1) // bs_)
        slot = self.free_slots.pop()
        new_pages = self._alloc_pages(total_need - n_pre)
        self._ref_pages(new_pages)
        pages = shared + new_pages
        self.stats["peak_pages"] = max(self.stats["peak_pages"],
                                       self._pages_in_use)
        req.pages = pages
        req.slot = slot
        req.t_admit = time.monotonic()
        self._admits.append((req.rid, req.t_enqueue, req.t_admit, true_len))
        req.tokens = list(prompt)  # the first token is appended at readback
        if n_pre:
            self.stats["prefix_hit_pages"] += n_pre
            _M_PREFIX_HIT.inc(n_pre)
        sampling = req.sampling
        if sampling[0] and req.key_base is None:
            # key_base = fold_in(PRNGKey(seed), rid): the request's own
            # stream root, so its sampled tokens are independent of which
            # co-tenants (or which replica) it landed with
            req.key_base = np.asarray(
                jax.random.fold_in(jax.random.PRNGKey(req.seed), req.rid))
        row = np.zeros(self.pages_per_seq, np.int32)
        row[:len(pages)] = pages
        self.page_table[slot] = row
        self.lengths[slot] = n_pre * bs_
        # a prefix-cache hit starts the stream past the shared pages
        self._prefilling[slot] = _PrefillState(req, pages, n_pre, digests,
                                               consumed=n_pre * bs_)
        self._active_sampling = sampling
        if ad is not None:
            self._slot_adapter[slot] = ad
            self._active_lora_rank = ad.rank
        if adm is not None:
            adm.end("ok", slot=slot, pages=len(pages),
                    prefix_hit_pages=n_pre)
        return "admitted"

    def _admit_from(self, queue):
        """Admit from the head of ``queue`` (a deque of EngineRequests)
        until one defers — FIFO, the batch path's no-skip-ahead contract
        (the frontend's scheduler reorders BEFORE requests reach this
        point). Pops every request that reached a terminal state."""
        admitted = False
        while queue and self.free_slots:
            status = self.try_admit_one(queue[0])
            if status == "deferred":
                break
            queue.popleft()
            admitted = True
        self._update_gauges()
        return admitted

    def step(self):
        """One scheduling round: sweep cancellations, advance the decode
        pipeline (pending prompt chunks ride in its dispatch), sweep timeouts.
        Returns the EngineRequests that reached a terminal state during
        this step; ``[]`` when idle.

        Decode pipeline: under ``async_decode`` the engine keeps ONE block
        in flight — block k+1 is dispatched chained off block k's device-
        resident last-token row BEFORE block k's tokens are read back, so
        the host emit/retire/admit work (and the caller's scheduling
        between step() calls) runs under block k+1's device execution.
        Retirement and admission stay at readback points; a slot whose
        request finished mid-block simply has its overshoot tokens
        discarded (its KV writes stay inside its still-held page
        reservation, and any page later reallocated is fully rewritten by
        the new tenant's prefill/decode before it is ever read). The sync
        path (``async_decode=False``) dispatches and reads back in one
        call — the pre-pipeline behavior, which tests alone set."""
        self._t_step0 = time.monotonic_ns()
        with _trace.annotation("serve.step"):
            try:
                return self._step_ragged()
            finally:
                self._phase(None)  # a raise left a phase open

    def _phase(self, name, step=None, stamp=None):
        """Move the dispatcher thread's phase annotation on — the open one
        closes and ``name`` (a span of STEP_PHASES, or None) opens
        — stamping ``step[stamp]`` on the step log's clock between them.
        For ``serve.pack`` → ``serve.decode``, which hand over inside a
        dispatch function where no ``with`` block can span them."""
        ann, self._phase_ann = self._phase_ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        if step is not None:
            step[stamp] = time.monotonic_ns()
        if name is not None:
            ann = self._phase_ann = _trace.annotation(name)
            ann.__enter__()

    def _begin_step(self, kind, k, chain):
        """The step record of the dispatch about to be packed."""
        step = _trace.new_step(
            engine=self._engine_seq, kind=kind, k=k,
            chained=chain is not None, t_step0=self._t_step0)
        self._phase("serve.pack", step, "t_pack0")
        return step

    def _step_ragged(self):
        """step()'s body (ISSUE 20): pending prompt chunks ride inside the
        decode dispatch itself (_dispatch_ragged), so a step is one
        dispatch + one readback whatever the admission mix, between a
        cancellation sweep and a timeout sweep."""
        # requests that retired under an out-of-band _settle_inflight
        # readback surface here, so the frontend's step-driven finish path
        # sees every terminal request exactly once
        retired = self._pending_retired
        self._pending_retired = []
        for slot in list(self._active):
            if self._active[slot].cancelled:
                retired.append(self._retire(slot))
        for slot in list(self._prefilling):
            if self._prefilling[slot].req.cancelled:
                retired.append(self._abort_prefill(slot))
        if self.async_decode:
            prev = self._inflight
            if prev is not None:
                self._inflight = self._dispatch_ragged(chain=prev)
                retired.extend(self._process_block(prev))
            if self._inflight is None and (self._active or self._prefilling):
                self._inflight = self._dispatch_ragged()
        elif self._active or self._prefilling:
            rec = self._dispatch_ragged()
            if rec is not None:
                retired.extend(self._process_block(rec))
        now = time.monotonic()
        for slot in list(self._active):
            r = self._active[slot]
            if r.timeout_s is not None and now - r.t_admit > r.timeout_s:
                self.stats["timed_out_requests"] += 1
                counters.bump("fault.serve.request_timeout")
                r.timed_out = True
                retired.append(self._retire(slot))
        for slot in list(self._prefilling):
            r = self._prefilling[slot].req
            if r.timeout_s is not None and now - r.t_admit > r.timeout_s:
                self.stats["timed_out_requests"] += 1
                counters.bump("fault.serve.request_timeout")
                retired.append(self._abort_prefill(slot, timed_out=True))
        self._update_gauges()
        # prompts that failed alone inside this step's mixed dispatch
        retired.extend(self._pending_retired)
        self._pending_retired = []
        return retired

    def _dispatch_ragged(self, chain=None):
        """Dispatch one ragged step: when prompt chunks are pending, the
        MIXED program carries them alongside every decode row; with no
        prefill in flight the fixed-k decode block (_dispatch_decode)
        runs alone."""
        if self._prefilling:
            return self._dispatch_ragged_mixed(chain)
        return self._dispatch_decode(chain=chain)

    def _dispatch_ragged_mixed(self, chain):
        """One mixed ragged dispatch: every decode row (one feed token
        each) plus up to ``_ragged_chunk`` prompt tokens of mid-prefill
        slots, packed into a single [T]-token program that then scans the
        remaining k-1 decode steps. Prompts landing their LAST chunk
        graduate here — the packed pass samples their first token and the
        scan decodes them alongside everyone else, so TTFT never waits
        for a separate prefill dispatch. Shortest-remaining-first chunk
        scheduling drains near-done prompts into the decode group ASAP."""
        sampling = self._active_sampling
        lora_rank = self._active_lora_rank
        state = self._captured_state()
        k = self.decode_block
        step = self._begin_step("mixed", k, chain)
        S = self.max_seqs
        T = self._ragged_tokens
        budget = self._ragged_chunk
        sched = []
        order = sorted(self._prefilling.items(),
                       key=lambda kv: (len(kv[1].req.prompt)
                                       - kv[1].consumed, kv[0]))
        for slot, st in order:
            if budget <= 0:
                break
            rem = len(st.req.prompt) - st.consumed
            take = min(rem, budget)
            budget -= take
            sched.append((slot, st, take, take == rem))
        covered = ({s for s, r in chain.rows
                    if self._active.get(s) is r} if chain is not None
                   else set())
        chunk_rows = {slot: (st, take, final)
                      for slot, st, take, final in sched}
        tok_block = np.zeros(T, np.int32)
        row_of = np.zeros(T, np.int32)
        token_pos = np.zeros(T, np.int32)
        valid = np.zeros(T, bool)
        use_last = np.zeros((S, 1), bool)
        q_lens = np.zeros(S, np.int32)
        lengths_op = np.zeros(S, np.int32)
        caps = np.zeros(S, np.int32)   # 0 = frozen/scratch-routed in scan
        bases = np.zeros((S, 2), np.uint32)
        idxs = np.zeros(S, np.int32)
        part = []    # decode participants: active rows + graduating rows
        grads = []   # (slot, st) graduating at THIS dispatch
        pos = 0
        for slot in range(S):
            r = self._active.get(slot)
            if r is not None:
                caps[slot] = len(r.prompt) + r.max_new_tokens - 1
                # host twin of the in-program freeze clamp: an over-budget
                # row's feed position must not index past its reservation
                base = min(int(self.lengths[slot]), int(caps[slot]))
                q_lens[slot] = 1
                lengths_op[slot] = base
                row_of[pos] = slot
                token_pos[pos] = base
                valid[pos] = True
                if slot in covered:
                    use_last[slot, 0] = True
                else:
                    tok_block[pos] = r.last_token
                if sampling[0]:
                    bases[slot] = r.key_base
                    idxs[slot] = r.n_dispatched
                part.append((slot, r))
                pos += 1
            elif slot in chunk_rows:
                st, take, final = chunk_rows[slot]
                req = st.req
                sl = slice(pos, pos + take)
                tok_block[sl] = req.prompt[st.consumed:st.consumed + take]
                row_of[sl] = slot
                token_pos[sl] = int(self.lengths[slot]) + np.arange(take)
                valid[sl] = True
                q_lens[slot] = take
                lengths_op[slot] = self.lengths[slot]
                pos += take
                if final:
                    caps[slot] = len(req.prompt) + req.max_new_tokens - 1
                    if sampling[0]:
                        bases[slot] = req.key_base  # idx 0: first token
                    part.append((slot, req))
                    grads.append((slot, st))
        cu = np.zeros(S + 1, np.int32)
        cu[1:] = np.cumsum(q_lens)
        walk = getattr(self._cache_spec, "ragged_walk", None)
        if walk is not None:  # K/V pages: the ragged kernel's grid, a layer
            self._log_walk(step, "ragged_walk", walk(
                self.pools[0], cu, lengths_op + q_lens, T,
                self.pages_per_seq))
        if k > 1:  # the scan's rows, as its first step finds them
            self._log_paged_walk(step, lengths_op + q_lens, caps)
        # non-participant rows (empty slots + still-mid-prefill prompts)
        # route their scan-step writes to the scratch page
        scan_pt = np.where((caps > 0)[:, None], self.page_table, 0)
        if chain is not None and use_last.any():
            last_dev = chain.last
        else:
            last_dev = jnp.zeros((S, 1), jnp.int32)
        if lora_rank is not None:
            ads = sorted({a.digest: a for a
                          in self._slot_adapter.values()}.values(),
                         key=lambda a: a.digest)
            a_stack, b_stack, l_scales, lpos = self._lora_stack(lora_rank,
                                                                ads)
            l_idx = np.zeros(S, np.int32)
            for slot, r in part:
                if r.adapter is not None:
                    l_idx[slot] = lpos[r.adapter.digest]
            l_idx = jnp.asarray(l_idx)

        def dispatch():
            chaos.site("serve.decode")
            args = (state, jnp.asarray(tok_block), jnp.asarray(cu),
                    jnp.asarray(row_of), jnp.asarray(token_pos),
                    jnp.asarray(valid), jnp.asarray(use_last), last_dev,
                    # a host copy: see _dispatch_decode on asarray aliasing
                    tuple(self.pools), jnp.asarray(self.page_table.copy()),
                    jnp.asarray(scan_pt), jnp.asarray(lengths_op),
                    jnp.asarray(caps), keys)
            if lora_rank is not None:
                return self._lora_ragged_fn(sampling, lora_rank)(
                    *args, a_stack, b_stack, l_scales, l_idx)
            return self._ragged_fn(sampling)(*args)

        progs = [("ragged", sampling) if lora_rank is None
                 else ("lora_ragged", sampling, lora_rank)]
        if sampling[0]:
            progs.append(("keys", k))
        host = None
        t0 = time.monotonic()
        try:
            with self._locked_dispatch(*progs):
                self._phase("serve.decode", step, "t_disp0")
                if sampling[0]:
                    idx_mat = (idxs[None, :]
                               + np.arange(k, dtype=np.int32)[:, None])
                    keys = _KEYS_FROM_BASE(jnp.asarray(bases),
                                           jnp.asarray(idx_mat))
                else:
                    keys = jnp.zeros((k, S, 2), jnp.uint32)
                blk, pools = self.retry_policy.run(dispatch,
                                                   name="serve.decode")
                self._phase(None, step, "t_disp1")
                if not self.async_decode:
                    host = self._read_back_in_lock(step, blk)
        except Exception as e:
            self._phase(None)
            if not _compilemem.is_oom(e):
                raise  # the decode seam's contract: an outage ends serve()
            # an OOM is the prompts' — the chunk tokens are what size this
            # dispatch, a decode row adds one token — so they fail ALONE,
            # after _locked_dispatch committed the forensics, and free all
            # they held. Decode rows that rode along are untouched (their
            # bookkeeping happens after a dispatch succeeds) and go out
            # again with the next step.
            for slot, _, _, _ in sched:
                self._pending_retired.append(self._fail_prefill(slot, e))
            return None
        self.pools = list(pools)  # lint: shared-mutation-without-lock-ok (engine fields are dispatcher-owned — single-threaded by contract)
        cold = self._last_dispatch_cold
        if _trace.enabled() and cold:
            _goodput.serving_note("compile", time.monotonic() - t0)
        n_chunk = sum(t for _, _, t, _ in sched)
        _dp = _devprof._PLANE
        if _dp is not None and not cold:
            prog_key = (f"serve.ragged[k{k},s{sampling}]"
                        if lora_rank is None else
                        f"serve.lora_ragged[r{lora_rank},k{k},s{sampling}]")
            _dp.tick(prog_key, t0, blk, tokens=k * len(part) + n_chunk,
                     context="serve.decode")
        last = blk[k - 1][:, None]
        if hasattr(blk, "copy_to_host_async"):
            blk.copy_to_host_async()
        # ---- bookkeeping: chunks consumed, graduations, dispatch counts
        for slot, st, take, final in sched:
            _M_CHUNKS.inc()
            st.consumed += take
            self.lengths[slot] += take
        for slot, st in grads:
            # graduation at DISPATCH: the packed pass sampled tok0 and the
            # scan is already decoding this row — it joins the group now;
            # all k of its tokens arrive at this block's readback
            del self._prefilling[slot]
            if self.enable_prefix_cache:
                self._index_prompt_pages(len(st.req.prompt), st.pages,
                                         st.n_pre0, st.digests)
            st.req.n_dispatched = 0
            self._active[slot] = st.req
        for slot, r in part:
            r.n_dispatched += k
            self.lengths[slot] += k
        for slot, st in grads:
            # decode invariant lengths = len(prompt) + n_dispatched - 1:
            # the packed pass wrote the prompt's KV (lengths += take above)
            # and each scan write lands one BEHIND its dispatch count (the
            # boundary token fed at position true_len, not true_len+1)
            self.lengths[slot] -= 1
        # the kernel's own extents: q_len tokens fed, kv_len = the row's
        # tokens AFTER this dispatch's first write (kv_lens = lengths + q)
        self._dispatched(step, cold, [
            *((r.rid, "d", 1, int(lengths_op[slot]) + 1)
              for slot, r in part if slot not in chunk_rows),
            *((st.req.rid, "g" if final else "c", take,
               int(lengths_op[slot]) + take)
              for slot, st, take, final in sched)])
        return _InflightBlock(blk, last, k, part, step, host=host, cold=cold)

    def _dispatch_decode(self, chain=None):
        """Dispatch ONE decode block over the current active set WITHOUT
        reading it back. ``chain`` is the still-in-flight previous block:
        its device-resident last-token row feeds this block for every
        slot it covered (the autoregressive dependency never round-trips
        to the host); freshly admitted slots merge their host-known first
        token in with one tiny fused select. Returns the new
        _InflightBlock, or None when nothing can dispatch — empty active
        set, or some row's token budget is fully dispatched (the caller
        must read the in-flight block back first so those rows retire)."""
        if not self._active:
            return None
        budgets = [r.max_new_tokens - r.n_dispatched
                   for r in self._active.values()]
        # Async pipeline: a block goes out while the LARGEST remaining
        # budget is positive — short-budget rows ride along under their
        # in-program length caps (their overshoot is discarded at emit).
        # Sync mode asks it of the smallest, as the pre-pipeline engine
        # did: there a spent row retired at the readback before.
        remaining = max(budgets) if self.async_decode else min(budgets)
        if remaining <= 0:
            return None  # every row fully dispatched: read back, retire
        sampling = self._active_sampling
        lora_rank = self._active_lora_rank
        state = self._captured_state()
        # ONE fixed block size (ISSUE 20): short-budget rows ride under
        # their in-program caps and overshoot is discarded at emit
        k = self.decode_block
        step = self._begin_step("decode", k, chain)
        rows = list(self._active.items())
        # a chained slot must still belong to the SAME request — a slot
        # retired and re-admitted while the block was in flight feeds its
        # new tenant's host-known token, not the dead tenant's device row
        covered = ({s for s, r in chain.rows
                    if self._active.get(s) is r} if chain is not None
                   else ())
        toks = np.zeros((self.max_seqs, 1), np.int32)
        fresh = np.zeros((self.max_seqs, 1), bool)
        bases = np.zeros((self.max_seqs, 2), np.uint32)
        idxs = np.zeros(self.max_seqs, np.int32)
        caps = np.zeros(self.max_seqs, np.int32)  # empty slots freeze at 0
        for slot, r in rows:
            # last page-reserved position: an over-budget row's writes
            # freeze here inside the program (see _decode_block_fn)
            caps[slot] = len(r.prompt) + r.max_new_tokens - 1
            if slot not in covered:
                toks[slot, 0] = r.last_token
                fresh[slot, 0] = True
            if sampling[0]:
                bases[slot] = r.key_base
                idxs[slot] = r.n_dispatched
        self._log_paged_walk(step, self.lengths, caps)
        if chain is None:
            feed = jnp.asarray(toks)
        elif fresh.any():
            feed = jnp.where(jnp.asarray(fresh), jnp.asarray(toks),
                             chain.last)
        else:
            feed = chain.last
        if lora_rank is not None:
            # lora group: fixed-depth stacked adapter operands + per-row
            # gather indices (0 = the zero slot for plain co-tenants).
            # Digest-sorted so the stack cache key — and row indexing —
            # is deterministic for a given working set.
            ads = sorted({a.digest: a for a
                          in self._slot_adapter.values()}.values(),
                         key=lambda a: a.digest)
            a_stack, b_stack, l_scales, pos = self._lora_stack(lora_rank,
                                                               ads)
            l_idx = np.zeros(self.max_seqs, np.int32)
            for slot, r in rows:
                if r.adapter is not None:
                    l_idx[slot] = pos[r.adapter.digest]
            l_idx = jnp.asarray(l_idx)
        # HOST copies first: on the CPU backend jnp.asarray aliases a
        # 64-byte-aligned numpy buffer zero-copy (and jnp.array only adds a
        # device-side copy that is itself enqueued), the dispatch is
        # asynchronous, and the bookkeeping below mutates both arrays in
        # place — a program queued behind a long one then read the NEXT
        # step's lengths (seen as tokens that differ only in some
        # processes: alignment decides the aliasing, load the timing)
        page_table = jnp.asarray(self.page_table.copy())
        lengths = jnp.asarray(self.lengths.copy())

        # the chaos site fires BEFORE the jitted call, so an injected
        # outage retries against intact pools; a real failure after the
        # dispatch donated them is not retriable (the retry would read
        # donated buffers) and raises out through the caller's cleanup
        def dispatch():
            chaos.site("serve.decode")
            if lora_rank is not None:
                if k == 1:
                    nxt, pools = self._lora_decode(sampling, lora_rank)(
                        state, feed, tuple(self.pools), page_table,
                        lengths, jnp.asarray(caps),
                        keys[0], a_stack, b_stack, l_scales, l_idx)
                    return nxt[None], pools
                return self._lora_block_fn(sampling, lora_rank, k)(
                    state, feed, tuple(self.pools), page_table, lengths,
                    jnp.asarray(caps), keys, a_stack, b_stack, l_scales,
                    l_idx)
            if k == 1:
                nxt, pools = self._decode(sampling)(
                    state, feed, tuple(self.pools), page_table,
                    lengths, jnp.asarray(caps), keys[0])
                return nxt[None], pools
            return self._decode_block_fn(sampling, k)(
                state, feed, tuple(self.pools), page_table, lengths,
                jnp.asarray(caps), keys)

        if lora_rank is not None:
            progs = [("lora_decode", sampling, lora_rank) if k == 1
                     else ("lora_block", sampling, lora_rank, k)]
        else:
            progs = [("decode", sampling) if k == 1
                     else ("block", sampling, k)]
        if sampling[0]:
            progs.append(("keys", k))
        host = None
        t0 = time.monotonic()  # the compile note's and devprof's epoch
        with self._locked_dispatch(*progs):
            self._phase("serve.decode", step, "t_disp0")
            if sampling[0]:
                idx_mat = idxs[None, :] + np.arange(k, dtype=np.int32)[:, None]
                keys = _KEYS_FROM_BASE(jnp.asarray(bases),
                                       jnp.asarray(idx_mat))
            else:
                # greedy ignores the keys entirely — skip the device work
                keys = jnp.zeros((k, self.max_seqs, 2), jnp.uint32)
            blk, pools = self.retry_policy.run(dispatch, name="serve.decode")
            self._phase(None, step, "t_disp1")
            if not self.async_decode:
                host = self._read_back_in_lock(step, blk)
        self.pools = list(pools)  # lint: shared-mutation-without-lock-ok (engine fields are dispatcher-owned — single-threaded by contract)
        cold = self._last_dispatch_cold
        if _trace.enabled() and cold:
            # a cold decode dispatch spent its wall tracing, not decoding —
            # the block's readback skips its 'decode' note (the cold flag
            # rides the _InflightBlock) so the same wall isn't counted twice
            _goodput.serving_note("compile", time.monotonic() - t0)
        _dp = _devprof._PLANE
        if _dp is not None and not cold:
            # device-time sampling (ISSUE 17): on cadence, ONE timed
            # dispatch — block on the token buffer inside devprof (the
            # devprof-seam) and bank device-seconds per emitted token
            # under the program's ledger key. Off cadence this is a
            # counter increment and the block stays fully async; cold
            # dispatches (compile wall) never enter the table.
            if lora_rank is not None:
                prog_key = (f"serve.lora_decode[r{lora_rank},s{sampling}]"
                            if k == 1 else
                            f"serve.lora_decode_block[r{lora_rank},k{k},"
                            f"s{sampling}]")
            else:
                prog_key = (f"serve.decode[s{sampling}]" if k == 1
                            else f"serve.decode_block[k{k},s{sampling}]")
            _dp.tick(prog_key, t0, blk, tokens=k * len(rows),
                     context="serve.decode")
        last = blk[k - 1][:, None]  # device row the NEXT block chains from
        if hasattr(blk, "copy_to_host_async"):
            blk.copy_to_host_async()  # transfer rides under the compute
        # dispatch-time accounting: for every SURVIVING slot this equals
        # what per-token emit accounting would produce (+k per block); a
        # slot that turns out to have finished mid-block is zeroed at
        # retire, so the overshoot never leaks
        self._dispatched(step, cold, [
            (r.rid, "d", 1, min(int(self.lengths[slot]), int(caps[slot])) + 1)
            for slot, r in rows])
        for slot, r in rows:
            r.n_dispatched += k
            self.lengths[slot] += k
        return _InflightBlock(blk, last, k, rows, step, host=host, cold=cold)

    def _read_back_in_lock(self, step, blk):
        """Sync mode (``async_decode=False``): the readback happens INSIDE
        the dispatch lock, exactly like the pre-pipeline engine — the lock
        covers the whole device round trip, which is what made replicas
        sharing a lock serialize their compute. The async path's readback
        is lock-free in _process_block."""
        step["t_sync0"] = time.monotonic_ns()
        with _trace.annotation("serve.decode.sync"):
            host = np.asarray(blk)  # serve-readback-ok
        step["t_ready"] = time.monotonic_ns()
        return host

    def _log_walk(self, step, name, walk):
        """`walk` = (walked, dense) grid steps onto the record and the
        engine's running sums."""
        step[name] = walk
        self.stats[name] = tuple(a + b for a, b in zip(self.stats[name], walk))

    def _log_paged_walk(self, step, lengths, caps):
        """The step log's `paged_walk`: the grid steps a layer and forward
        of this dispatch's first scan step walks in the paged decode kernel
        beside the live rows x longest row's blocks walk, where the cache
        spec counts them (float K/V pages)."""
        walk = getattr(self._cache_spec, "paged_walk", None)
        got = walk and walk(
            self.pools, np.where(caps > 0, np.minimum(lengths, caps) + 1, 0),
            self.pages_per_seq)
        if got:
            self._log_walk(step, "paged_walk", got)

    def _dispatched(self, step, cold, rows):
        """The dispatch went out: its rows ``(rid, role, q_len, kv_len)``
        and the admissions since the previous record go on its record."""
        step["cold"] = cold
        step["rows"] = rows
        if self._cache_spec.log_pages:  # pages in use, of those allocatable
            step["pages"] = (self._pages_in_use, self.num_pages - 1)
        if self._cache_spec.has_state:  # rows holding a state slot, of all
            step["slots"] = (len(self._active) + len(self._prefilling),
                             self.max_seqs)
        step["admits"] = list(self._admits)
        self._admits.clear()

    def _process_block(self, rec):
        """The decode pipeline's designated readback point: block tokens
        come to the host, per-request emit/retire runs, TPOT lands, and the
        dispatch's step record is completed and committed."""
        step = rec.step
        if rec.host is not None:
            block = rec.host  # sync mode stamped its readback in the lock
        else:
            step["t_sync0"] = time.monotonic_ns()
            with _trace.annotation("serve.decode.sync"):
                try:
                    block = np.asarray(rec.blk)  # serve-readback-ok
                except Exception as e:
                    # async-path OOM surfaces at readback, outside the
                    # dispatch lock — same forensics seam as
                    # _locked_dispatch
                    _compilemem.maybe_oom_report(
                        e, program="serve.decode_block")
                    raise
            step["t_ready"] = time.monotonic_ns()
        if block.shape[0] > rec.k:
            step["counters"] = dict(zip(
                self.model.serving_counter_names,
                block[rec.k:].reshape(-1).tolist()))
        t_ready = step["t_ready"]
        # the block's OWN interval, normalized per token: with one block
        # always in flight the device starts this one when the previous
        # one's tokens became ready, not when this one was dispatched
        # (dispatch→readback would span two blocks)
        block_wall = (t_ready - max(step["t_disp1"], self._t_prev_ready)) / 1e9
        self._t_prev_ready = t_ready
        _M_TPOT.observe(block_wall / rec.k)
        if _trace.enabled() and not rec.cold:
            # serving goodput: the decode slice (under async overlap it
            # runs concurrently with host_emit/admit — the split reports
            # attribution, not a partition of wall clock). A cold block
            # already landed in 'compile' at dispatch.
            _goodput.serving_note("decode", block_wall)
        self.stats["decode_steps"] += rec.k
        retired = []
        emits = step["emits"] = []
        with _trace.annotation("serve.emit"):
            for slot, r in rec.rows:
                if r.finished or self._active.get(slot) is not r:
                    # retired while in flight (cancel/timeout/reroute):
                    # its overshoot tokens are discarded
                    emits.append((r.rid, 0))
                    continue
                if r.t_first_token is None:
                    # graduation: the first token materializes at THIS
                    # readback (an adopted request arrives with its stamp)
                    now_ft = time.monotonic()
                    r.t_first_token = now_ft
                    _M_TTFT.observe(now_ft - r.t_enqueue)
                    if r.trace is not None:
                        r.trace.event("first_token",
                                      ttft_s=round(now_ft - r.t_enqueue, 6))
                if r.trace is not None:
                    # the request's view of this fused decode dispatch
                    r.trace.span_at("decode_block", block_wall, block_wall,
                                    k=rec.k)
                emitted = 0
                for s in range(rec.k):
                    tok = int(block[s, slot])
                    r.tokens.append(tok)
                    r.n_generated += 1
                    r.last_token = tok
                    emitted += 1
                    if r.on_token is not None:
                        r.on_token(r.rid, tok)
                    if r.n_generated >= r.max_new_tokens or (
                            r.eos_token_id is not None
                            and tok == r.eos_token_id):
                        # mid-block EOS: rest of the block is discarded
                        retired.append(self._retire(slot))
                        break
                _M_TOKENS.inc(emitted)
                emits.append((r.rid, emitted))
                if r.trace is not None:
                    r.trace.event("emit", tokens=emitted,
                                  n_generated=r.n_generated)
        step["t_emit1"] = time.monotonic_ns()
        _trace.commit_step(step, STEP_PHASES)
        if _trace.enabled():
            _goodput.serving_note("host_emit",
                                  (step["t_emit1"] - t_ready) / 1e9)
            # not a span(): span.serve.admit_s holds the batch path's sweeps
            for rid, t_enqueue, t_admit, n_prompt in step["admits"]:
                _trace.emit_record(_trace.span_record(
                    "serve.admit", t_enqueue * 1e9, t_admit * 1e9,
                    "serve.step", step=step["seq"], rid=rid,
                    n_prompt=n_prompt))
        return retired

    def drain(self):
        """Finish every admitted request WITHOUT admitting more; returns the
        retired EngineRequests. The frontend's replica-drain building block,
        and the escape hatch before calling batch serve() on an engine that
        still has online work in flight."""
        out = []
        while (self._active or self._prefilling
               or self._inflight is not None or self._pending_retired):
            out.extend(self.step())
        return out

    @staticmethod
    def _per_request(value, n, name):
        """Scalar | per-rid list | complete {rid: v} dict -> per-rid list
        (satellite: per-request max_new_tokens)."""
        if isinstance(value, dict):
            missing = [i for i in range(n) if i not in value]
            if missing:
                raise ValueError(
                    f"per-request {name} dict missing rids {missing}")
            return [int(value[i]) for i in range(n)]
        if isinstance(value, (list, tuple, np.ndarray)):
            if len(value) != n:
                raise ValueError(f"per-request {name} has {len(value)} "
                                 f"entries for {n} requests")
            return [int(v) for v in value]
        return [int(value)] * n

    def serve(self, prompts, max_new_tokens, eos_token_id=None,
              do_sample=False, temperature=1.0, top_k=0, top_p=1.0, seed=0,
              on_token=None, request_timeout_s=None, sampling_overrides=None,
              adapters=None):
        """Serve a list of int32 prompt arrays; returns a list of
        [len(prompt) + n_generated] arrays (stops at eos or max_new_tokens).
        Requests beyond the pool/slot capacity queue and join as earlier
        sequences retire — continuous batching.

        ``max_new_tokens`` is a scalar, a per-request list, or a complete
        {rid: n} dict. ``sampling_overrides`` (per-request list of dicts /
        None, or a partial {rid: dict}) overrides do_sample/temperature/
        top_k/top_p per request; requests sharing a sampling tuple
        co-schedule, others wait for the running group (the sampler is a
        compile-time constant of the decode program).

        Degradation contract (one request must never kill the batch):

        - a request whose PREFILL raises fails alone: its slot/pages free,
          its results entry is None, the exception lands in
          self.request_errors[rid] (and on the EngineRequest's
          error/error_message), and every co-tenant keeps serving;
        - a request that can NEVER fit the pool (needs more pages than
          exist) likewise fails alone instead of raising out of serve() —
          admission backpressure for merely-busy pools is unchanged
          (FIFO deferral, stats["deferred_admissions"]);
        - request_timeout_s bounds each request's wall-clock from admission:
          on expiry it retires with the tokens generated so far
          (stats["timed_out_requests"]) — the slot goes back to the queue's
          next request instead of a straggler pinning it forever.

        Sampling (do_sample/temperature/top_k/top_p — the dense generate()
        sampler math) draws each sequence from its OWN key stream
        fold_in(fold_in(seed, request_id), token_index), so a request's
        output is reproducible regardless of which co-tenants shared its
        batch.

        on_token(request_id, token_id) streams each generated token (incl.
        the prefill's first token) as soon as its decode step completes —
        the serving-callback hook for SSE-style responses.

        ``adapters`` (ISSUE 19) attaches per-request LoRA adapters: a
        single resolved ``serving.adapters.LoRAAdapter`` applied to every
        request, a per-request list (None entries = base model), or a
        sparse {rid: adapter} dict. Adapter requests co-schedule with
        same-rank adapter requests and with base requests riding the zero
        slot; a batch with NO adapters dispatches the untouched base
        programs byte-for-byte."""
        if self._active or self._prefilling or self._inflight is not None:
            raise RuntimeError(
                "serve() on an engine with active online requests — drain() "
                "the frontend-driven work first")
        default_sampling = canonical_sampling(do_sample, temperature,
                                              top_k, top_p)
        per_new = self._per_request(max_new_tokens, len(prompts),
                                    "max_new_tokens")
        # sampling_overrides dicts may be sparse ({rid: ov} for just the
        # requests that deviate), but a list must cover every request —
        # fail like _per_request does, not with a bare IndexError mid-build
        if (sampling_overrides is not None
                and not isinstance(sampling_overrides, dict)
                and len(sampling_overrides) != len(prompts)):
            raise ValueError(
                f"per-request sampling_overrides has "
                f"{len(sampling_overrides)} entries for "
                f"{len(prompts)} requests")
        # adapters: one-for-all object, per-request list, or sparse dict —
        # same shape rules as sampling_overrides (lists must cover every
        # request; dicts may be sparse)
        if (adapters is not None and isinstance(adapters, (list, tuple))
                and len(adapters) != len(prompts)):
            raise ValueError(
                f"per-request adapters has {len(adapters)} entries for "
                f"{len(prompts)} requests")
        # every serve() batch starts from a FRESH capture (old-code parity):
        # the version-keyed reuse below it only has to bridge admissions
        # and decode blocks within one batch / online stretch. Under the
        # compile lock: a sibling replica tracing the shared model must
        # not leak tracers into this walk (see _captured_state).
        ver = _core.tensor_mutation_version()
        with _COMPILE_LOCK:
            state = self.model.raw_state_dict()
        self._decode_state_cache = (ver, state)
        if self.enable_prefix_cache:
            self._refresh_cache_guard(state)
        reqs = []
        for rid, p in enumerate(prompts):
            samp = default_sampling
            if sampling_overrides is not None:
                ov = (sampling_overrides.get(rid)
                      if isinstance(sampling_overrides, dict)
                      else sampling_overrides[rid])
                if ov:
                    samp = canonical_sampling(
                        ov.get("do_sample", do_sample),
                        ov.get("temperature", temperature),
                        ov.get("top_k", top_k), ov.get("top_p", top_p))
            if adapters is None:
                ad = None
            elif isinstance(adapters, dict):
                ad = adapters.get(rid)
            elif isinstance(adapters, (list, tuple)):
                ad = adapters[rid]
            else:
                ad = adapters
            reqs.append(EngineRequest(
                rid, p, per_new[rid], eos_token_id=eos_token_id,
                sampling=samp, seed=seed, timeout_s=request_timeout_s,
                on_token=on_token, adapter=ad))
        # only after EVERY request constructed (construction validates and
        # can raise): escalating the error bound or counting requests first
        # would leak past the finally below, which only runs once the try
        # is entered
        self.request_errors = {}  # lint: shared-mutation-without-lock-ok (serve() owns the engine for the batch — single caller by contract)
        # every failed rid of THIS batch keeps its entry, however large
        self._request_errors_bound = max(1024, len(prompts))
        queue = deque(reqs)
        _M_QUEUE.set(len(queue))  # records the load peak via the gauge hwm
        try:
            with _trace.span("serve.admit"):
                self._admit_from(queue)
            _M_QUEUE.set(len(queue))
            while (queue or self._active or self._prefilling
                   or self._inflight is not None):
                if not (self._active or self._prefilling
                        or self._inflight is not None):
                    # an idle engine always resolves its queue head (admit
                    # or fail-alone) — reaching here means the admission
                    # invariant broke, and spinning would hang the caller
                    raise AssertionError(
                        "serve(): admission stalled with an idle engine")
                self.step()
                with _trace.span("serve.admit"):
                    self._admit_from(queue)
                _M_QUEUE.set(len(queue))
            return [r.result for r in reqs]
        finally:
            self._request_errors_bound = 1024
            # a raising on_token (or any mid-serve failure) must not leak a
            # warm engine's pages/slots: retire whatever is still active
            # (and drop any unprocessed in-flight block — its tokens are
            # lost with the requests they belonged to)
            self._inflight = None
            for slot in list(self._active):
                self._retire(slot)
            for slot in list(self._prefilling):
                self._abort_prefill(slot)
