"""Continuous-batching serving engine: request queue -> priced buckets ->
slot-reuse decode.

The serving hot path the bucketing model prices (DESIGN.md §10):

* **Admission** pops queued requests into free *slots* of a fixed-size
  decode batch.  With a :class:`~repro.core.bucketing.BucketPlan`, prompts
  are right-padded to their bucket edge — one prefill executable per edge,
  not per ragged length — and each row reads its logits out at its true
  last token (``last_pos``; causal attention makes the padded tail
  invisible).  Padding is only exact for attention families: SSM/hybrid
  state would integrate the pad tokens, so those run unpadded (exact,
  per-length compiles).
* **Decode** is one step-synchronous jitted call over all slots with a
  *per-slot position vector* — freshly admitted rows coexist with rows
  deep into generation; each row masks its own prefix and writes KV at its
  own offset.  Finished rows free their slot mid-flight and the next
  request is admitted without stopping the batch.
* **Warm-up**: every bucket edge's step GEMMs are selected in ONE
  ``select_gemm_config_batch`` call before serving, so the cold selection
  cost is paid once, vectorized, instead of per-shape on the first request.

Fail-soft semantics are PR 5's, unchanged: every prefill/decode is
transient-retried (the fault hook fires BEFORE the donated-cache decode,
so a retried step replays an intact cache), a
:class:`~repro.runtime.fault_tolerance.PreemptionGuard` drains cleanly at
the loop top, and a faulted run's emitted tokens are a bit-exact prefix of
the clean run's (sampling keys are pre-split per global step, so a retry
or drain never shifts the key stream).

The decode loop never round-trips to the host: sampled tokens stay on
device (one stack at end of run), RNG keys are pre-split in chunks, and
the loop blocks only at ``sync_every`` boundaries, where it stamps the
host clock (``step_ready_s``: every step's ready time at
``sync_every=1``).

With a tracer installed (``repro.obs.trace``), ``run()`` records its phases
as spans on the ``engine`` track (DESIGN.md §11): ``init``, ``admit``,
``upload``, ``decode_dispatch`` (which holds ``hook``), ``bookkeeping``,
``sync`` and ``collect``.  A ``Tracer(profiler=True)`` puts them into the
JAX profiler's trace as ``engine.<name>``.  With none installed each call
site costs one ``is None`` check and allocates nothing.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import meshctx
from repro.core.bucketing import BucketPlan, step_gemms
from repro.core.selector import (get_residual_corrector,
                                 select_gemm_config_batch)
from repro.core.simulator import simulate_gemm
from repro.core.topology import topology_fingerprint
from repro.distributed.sharding import cache_shardings
from repro.kernels import ops
from repro.nn.model import Model
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.drift import get_drift_monitor, record_step_drift
from repro.obs.metrics import MetricsRegistry
from repro.runtime.fault_tolerance import PreemptionGuard, retry

_STEP_RETRIES = 2
_STEP_BASE_DELAY = 0.01
_STEP_MAX_DELAY = 0.1
_KEY_CHUNK = 64


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # (len,) int32 token ids
    max_new_tokens: int                 # tokens to emit (incl. prefill's)
    extras: Optional[Dict] = None
    t_submit: float = 0.0               # host clock at submit()


@dataclass
class RequestResult:
    rid: int
    prompt_len: int
    padded_len: int                     # == prompt_len when unpadded
    tokens: np.ndarray                  # (n,) generated ids, n<=max_new
    admit_step: int                     # global step of first decode
    finish_step: int                    # global step after last decode
    finished: bool                      # False when drained mid-flight
    t_submit: float = 0.0               # host clock (perf_counter): submit
    t_admit: float = 0.0                # and its pop from the queue


@dataclass
class _Slot:
    rid: int = -1
    pos: int = 0                        # next KV write offset for this row
    remaining: int = 0
    admit_step: int = 0

    @property
    def active(self) -> bool:
        return self.rid >= 0


class ServingEngine:
    """One model, one decode batch of ``max_batch`` slots, FIFO admission.

    ``plan`` (optional) buckets ragged prompt lengths; without it every
    distinct length prefills at its exact shape.  ``decode_fault`` is the
    fault-injection hook: called as ``decode_fault(step, guard)`` at the
    top of every decode attempt, before the cache is donated.

    ``mesh`` (optional) is the mesh ``params`` are sharded over: the decode
    cache is laid out by ``cache_shardings`` and kept there across steps,
    and the mesh is installed (``repro.meshctx``) while the engine traces
    and runs, which puts the kernels under their shard_map boundary.

    The kernel backend is read when a program is traced, and jit's trace
    cache does not key on it, so each engine jits closures of its own: an
    engine built under another backend never reuses this one's traces."""

    def __init__(self, model: Model, params: Dict, *,
                 max_batch: int, max_len: int,
                 plan: Optional[BucketPlan] = None,
                 temperature: float = 0.0, seed: int = 0,
                 sync_every: int = 8,
                 decode_fault: Optional[Callable[..., None]] = None,
                 quiet: bool = False, mesh: Optional[Mesh] = None):
        cfg = model.cfg
        if plan is not None and cfg.family in ("ssm", "hybrid"):
            raise ValueError(
                f"bucketed (padded) admission is not exact for family "
                f"{cfg.family!r}: recurrent state integrates pad tokens. "
                f"Run without a plan (exact, per-length compiles).")
        self.model = model
        self.params = params
        self.plan = plan
        self.max_batch = int(max_batch)
        self.max_len = int(max_len)
        self.temperature = float(temperature)
        self.sync_every = max(int(sync_every), 1)
        self.decode_fault = decode_fault
        self._queue: List[Request] = []
        self._next_rid = 0
        self._base_key = jax.random.PRNGKey(seed)
        self._key_chunks: Dict[int, jax.Array] = {}
        self.retries = 0
        self.quiet = bool(quiet)
        # Per-run metrics registry (DESIGN.md §11): ``run()`` rebuilds it,
        # backs the integer stats counters with it, and merge-publishes it
        # into the process-global registry when metrics are enabled.  Kept
        # as an attribute so ``launch/serve.py`` can export it afterwards.
        self.run_registry: MetricsRegistry = MetricsRegistry()
        # Modeled one-decode-step latency at M = max_batch (the drift
        # monitor's prediction for each sync window); filled by warm_start.
        self.predicted_step_s: Optional[float] = None

        self.mesh = mesh
        B, S = self.max_batch, self.max_len
        cache_sh = rep = None               # None: sharding left to XLA
        if mesh is not None:
            cache_sh = cache_shardings(model.cache_specs(B, S), mesh, cfg)
            rep = NamedSharding(mesh, P())
        self._tokens_sharding = rep
        self._init_cache = jax.jit(lambda: model.init_cache(B, S),
                                   out_shardings=cache_sh)
        self._prefill = jax.jit(lambda *a: model.prefill(*a))
        self._decode = jax.jit(lambda *a: model.decode_step(*a),
                               donate_argnums=(1,),
                               out_shardings=(rep, cache_sh))
        if self.temperature > 0:
            t = self.temperature

            def _sample(logits, key):
                return jax.random.categorical(key, logits / t, axis=-1)
        else:
            def _sample(logits, key):
                return jnp.argmax(logits, axis=-1)
        self._sample = jax.jit(_sample)

        def _insert(full, part, b):
            def one(dst, src):
                start = (jnp.int32(0), b) + (jnp.int32(0),) * (dst.ndim - 2)
                return jax.lax.dynamic_update_slice(
                    dst, src.astype(dst.dtype), start)
            return jax.tree_util.tree_map(one, full, part)
        self._insert = jax.jit(_insert, donate_argnums=(0,),
                               out_shardings=cache_sh)

    # -- queue -------------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int,
               extras: Optional[Dict] = None) -> int:
        """Enqueue one request; returns its rid.  Validates against the
        engine's KV budget up front so admission can't overflow the cache."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {max_new_tokens}")
        padded = (self.plan.bucket_for(prompt.size) if self.plan
                  else prompt.size)
        if padded + max_new_tokens - 1 > self.max_len:
            raise ValueError(
                f"request needs {padded}+{max_new_tokens - 1} cache rows "
                f"> max_len {self.max_len}")
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append(Request(rid=rid, prompt=prompt,
                                   max_new_tokens=int(max_new_tokens),
                                   extras=extras,
                                   t_submit=time.perf_counter()))
        return rid

    # -- warm-up -----------------------------------------------------------

    def warm_start(self) -> int:
        """Prime the selector for every shape the serving path will launch:
        each bucket edge's (or queued length's) step GEMMs plus the decode
        batch's, in ONE batched selection call.  Returns shapes primed."""
        cfg = self.model.cfg
        if cfg.family == "ssm":
            return 0                          # no attention-step GEMM grid
        gemms = step_gemms(
            cfg.d_model, cfg.d_ff,
            kv_dim=cfg.num_kv_heads * cfg.head_dim,
            vocab=cfg.vocab_size,
            swiglu=cfg.activation == "swiglu")
        ms = set(self.plan.edges if self.plan
                 else {int(r.prompt.size) for r in self._queue})
        ms.add(self.max_batch)                # the decode step's M extent
        shapes = [(m, n, k) for m in sorted(ms) for (n, k) in gemms]
        hw = ops.get_default_hardware()
        with obs_trace.span("warm_start", cat="engine", track="engine",
                            args={"n_shapes": len(shapes)}):
            sels = select_gemm_config_batch(shapes, hw=hw)
        # The decode step's modeled latency: the summed priced latency of
        # its step GEMMs at M = max_batch — the drift monitor's prediction
        # for every measured sync window.
        self.predicted_step_s = sum(
            s.predicted.total for s, (m, _n, _k) in zip(sels, shapes)
            if m == self.max_batch)
        # Per-GEMM drift rows (site "warm_gemm"): when a drift monitor is
        # installed, check every warm selection's priced latency against
        # the event simulator.  Unlike the whole-step decode rows (config
        # None), these carry a config AND the topology fingerprint — the
        # residual corrector's training set (DESIGN.md §12), emitted for
        # free on every traced serving run.
        mon = get_drift_monitor()
        if mon is not None:
            for s in sels:
                try:
                    meas = simulate_gemm(s.problem, s.config, hw).time
                except (ValueError, RuntimeError):
                    continue
                mon.record_selection(s, meas, site="warm_gemm")
        return len(shapes)

    # -- serving loop ------------------------------------------------------

    def _key(self, step: int) -> jax.Array:
        c, r = divmod(step, _KEY_CHUNK)
        chunk = self._key_chunks.get(c)
        if chunk is None:
            chunk = self._key_chunks[c] = jax.random.split(
                jax.random.fold_in(self._base_key, c), _KEY_CHUNK)
        return chunk[r]

    def _status(self, msg: str) -> None:
        obs_trace.event("status", cat="engine", track="engine",
                        args={"msg": msg})
        if not self.quiet:
            print(f"[engine] {msg}")

    def _count_retry(self, attempt: int, err: Exception) -> None:
        self.retries += 1
        self.run_registry.counter("engine_retries").inc()
        obs_metrics.inc("engine_retries")
        obs_trace.event("step_retry", cat="fault", track="engine",
                        args={"attempt": attempt + 1, "error": repr(err)})
        self._status(f"transient fault absorbed "
                     f"(attempt {attempt + 1}): {err!r}")

    def _mesh_scope(self):
        return (meshctx.use_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())

    def _padded(self, prompt: np.ndarray
                ) -> Tuple[jax.Array, Optional[jax.Array], int]:
        """A prompt right-padded to its bucket edge: (tokens (1, padded),
        last_pos (None when unpadded), padded length)."""
        plen = int(prompt.size)
        padded = (self.plan.bucket_for(plen) if self.plan else plen)
        toks = np.zeros((1, padded), np.int32)
        toks[0, :plen] = prompt
        last_pos = (jnp.asarray([plen - 1], jnp.int32)
                    if padded != plen else None)
        return jnp.asarray(toks), last_pos, padded

    def _fresh_tokens(self) -> jax.Array:
        tokens = jnp.zeros((self.max_batch,), jnp.int32)
        if self._tokens_sharding is not None:
            tokens = jax.device_put(tokens, self._tokens_sharding)
        return tokens

    def probe(self, prompts, next_tokens=None
              ) -> Tuple[np.ndarray, np.ndarray]:
        """Prefill ``prompts`` (at most ``max_batch``) into slots 0..n-1 of
        a fresh cache, then run one decode step over all slots, each at its
        own position, feeding ``next_tokens`` (default: each prompt's greedy
        token).  Returns (prefill logits (n, V), decode logits (n, V)) as
        float32.  It runs the engine's own programs at the shapes it serves
        (each prompt padded to its bucket), so at served lengths it compiles
        nothing new: the way to hold one backend against another on the
        same weights."""
        prompts = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
        n = len(prompts)
        if not 0 < n <= self.max_batch:
            raise ValueError(f"probe takes 1..{self.max_batch} prompts, "
                             f"got {n}")
        pos = np.zeros((self.max_batch,), np.int32)
        with self._mesh_scope():
            cache = self._init_cache()
            first = []
            for b, prompt in enumerate(prompts):
                toks, last_pos, _ = self._padded(prompt)
                logits, pc = self._prefill(self.params, toks, None, last_pos)
                cache = self._insert(cache, pc, jnp.int32(b))
                first.append(logits[0])
                pos[b] = prompt.size
            first = jnp.stack(first)
            nxt = (jnp.argmax(first, axis=-1) if next_tokens is None
                   else jnp.asarray(next_tokens)).astype(jnp.int32)
            tokens = self._fresh_tokens().at[:n].set(nxt)
            dec, _ = self._decode(self.params, cache, tokens,
                                  jnp.asarray(pos))
        return (np.asarray(first, np.float32),
                np.asarray(dec[:n], np.float32))

    def lower_decode(self):
        """The decode step lowered at the engine's serving shapes (nothing
        runs or compiles) — for inspecting what the step launches."""
        with self._mesh_scope():
            B = self.max_batch
            tokens = jax.ShapeDtypeStruct((B,), jnp.int32,
                                          sharding=self._tokens_sharding)
            pos = jax.ShapeDtypeStruct((B,), jnp.int32)
            return self._decode.lower(self.params,
                                      jax.eval_shape(self._init_cache),
                                      tokens, pos)

    def run(self) -> Dict:
        """Serve the queue to completion (or preemption drain); returns the
        stats dict (see DESIGN.md §10 for the schema)."""
        with self._mesh_scope():
            return self._run()

    def _run(self) -> Dict:
        B = self.max_batch
        tr = obs_trace.get_tracer()
        on = tr is not None            # call sites build span args only then

        def span(name: str, args: Optional[Dict] = None):
            if tr is None:
                return obs_trace.NULL_SPAN
            return tr.span(name, cat="engine", track="engine", args=args)

        slots = [_Slot() for _ in range(B)]
        with span("init"):
            cache = self._init_cache()
            tokens = self._fresh_tokens()
        pos_host = [0] * B
        tok_log: List[jax.Array] = []        # per-step (B,) device arrays
        owners: List[Tuple[int, ...]] = []   # per-step slot->rid snapshot
        first_tok: Dict[int, jax.Array] = {}  # rid -> (1,) prefill token
        # rid -> (prompt_len, padded_len, admit step, t_submit, t_admit)
        meta: Dict[int, Tuple[int, int, int, float, float]] = {}
        finished: Dict[int, int] = {}        # rid -> finish_step
        # Per-run metrics registry: the integer stats accumulators ARE
        # registry counters now (same arithmetic, so the public stats dict
        # stays bit-identical); merged into the process-global registry at
        # run end when metrics are enabled.
        reg = self.run_registry = MetricsRegistry()
        c_real = reg.counter("engine_real_rows")
        c_padded = reg.counter("engine_padded_rows")
        drift_on = (self.predicted_step_s is not None
                    and get_drift_monitor() is not None)
        topo_fp = (topology_fingerprint(ops.get_default_hardware())
                   if drift_on else "")
        t_admit_s = 0.0                      # host time spent admitting
        dispatch_acc: List[float] = []       # per-step decode dispatch (s)
        ready: List[Tuple[int, float]] = []  # (steps done, host clock)
        drained = False
        step = 0
        t_sync = None

        def admit(b: int) -> None:
            nonlocal t_admit_s, tokens, cache
            t0 = time.perf_counter()
            req = self._queue.pop(0)
            plen = int(req.prompt.size)
            padded = self.plan.bucket_for(plen) if self.plan else plen
            with span("admit", on and {
                    "rid": req.rid, "slot": b, "prompt_len": plen,
                    "padded_len": padded,
                    "queue_wait_ms": (t0 - req.t_submit) * 1e3,
                    "queue_depth": len(self._queue)}):
                prompt, last_pos, _ = self._padded(req.prompt)
                logits, pc = retry(
                    lambda: self._prefill(self.params, prompt,
                                          req.extras or None, last_pos),
                    retries=_STEP_RETRIES, base_delay=_STEP_BASE_DELAY,
                    max_delay=_STEP_MAX_DELAY, on_retry=self._count_retry)
                cache = self._insert(cache, pc, jnp.int32(b))
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # (1,)
                tokens = tokens.at[b].set(tok[0])
            t_admit_s += time.perf_counter() - t0
            first_tok[req.rid] = tok
            slots[b].rid = req.rid
            slots[b].pos = plen
            slots[b].remaining = req.max_new_tokens - 1
            slots[b].admit_step = step
            pos_host[b] = plen
            meta[req.rid] = (plen, padded, step, req.t_submit, t0)
            reg.counter("engine_bucket_hits",
                        labels={"edge": str(padded)}).inc()
            c_real.inc(plen)
            c_padded.inc(padded)
            if slots[b].remaining == 0:       # single-token request
                finished[req.rid] = step
                slots[b].rid = -1

        t_run0 = time.perf_counter()
        with PreemptionGuard() as guard:
            while True:
                if guard.should_stop:
                    if any(s.active for s in slots) or self._queue:
                        drained = True
                        self._status(f"preemption requested; draining "
                                     f"after {step} decode steps")
                    break
                for b in range(B):
                    if not slots[b].active and self._queue:
                        admit(b)
                if not any(s.active for s in slots):
                    break
                this_step = step
                with span("upload", on and {"step": this_step}):
                    pos_dev = jnp.asarray(pos_host, jnp.int32)

                def body():
                    # Fault hook fires BEFORE decode: a retried step
                    # replays an intact (not-yet-donated) cache.
                    if self.decode_fault is not None:
                        with span("hook", on and {"step": this_step}):
                            self.decode_fault(this_step, guard)
                    return self._decode(self.params, cache, tokens, pos_dev)

                td0 = time.perf_counter()
                # Closes when the step is dispatched, not when it is done:
                # ``sync`` ends when it is.
                with span("decode_dispatch", on and {
                        "step": this_step,
                        "active": sum(1 for s in slots if s.active)}):
                    logits, cache = retry(
                        body, retries=_STEP_RETRIES,
                        base_delay=_STEP_BASE_DELAY,
                        max_delay=_STEP_MAX_DELAY,
                        on_retry=self._count_retry)
                    tokens = self._sample(logits, self._key(step)
                                          ).astype(jnp.int32)
                dispatch_acc.append(time.perf_counter() - td0)
                with span("bookkeeping", on and {"step": this_step}):
                    tok_log.append(tokens)
                    owners.append(tuple(s.rid for s in slots))
                    for b in range(B):
                        s = slots[b]
                        if not s.active:
                            continue
                        s.pos += 1
                        pos_host[b] = s.pos
                        s.remaining -= 1
                        if s.remaining == 0:
                            finished[s.rid] = step + 1
                            s.rid = -1        # slot free: reused next admit
                step += 1
                if step % self.sync_every:
                    continue
                with span("sync", on and {"step": this_step}):
                    tokens.block_until_ready()
                with span("bookkeeping", on and {"step": this_step}):
                    now = time.perf_counter()
                    ready.append((step, now))
                    window = now - (t_sync if t_sync is not None else t_run0)
                    t_sync = now
                    if obs_metrics.metrics_enabled():
                        obs_metrics.set_gauge("engine_queue_depth",
                                              len(self._queue))
                        obs_metrics.set_gauge(
                            "engine_slot_occupancy",
                            sum(1 for s in slots if s.active) / B)
                    if drift_on:
                        n = min(self.sync_every, len(dispatch_acc))
                        record_step_drift(
                            site="decode_step", shape=(B,),
                            predicted_s=self.predicted_step_s,
                            measured_s=window / max(n, 1), topo=topo_fp,
                            step=step,
                            dispatch_s=sum(dispatch_acc[-n:]) / max(n, 1))
        rem = step % self.sync_every
        with (span("sync", on and {"step": step - 1}) if rem
              else obs_trace.NULL_SPAN):
            jax.block_until_ready(tokens)
        now = time.perf_counter()
        t_decode = now - t_run0
        if rem:                   # tail window shorter than sync_every
            ready.append((step, now))
            if drift_on:
                window = now - (t_sync if t_sync is not None else t_run0)
                record_step_drift(
                    site="decode_step", shape=(B,),
                    predicted_s=self.predicted_step_s,
                    measured_s=window / rem, topo=topo_fp,
                    step=step, dispatch_s=sum(dispatch_acc[-rem:]) / rem)

        with span("collect"):
            # One transfer for the whole run: stack the device-side step log.
            decoded = (np.asarray(jnp.stack(tok_log)) if tok_log
                       else np.zeros((0, B), np.int32))
            firsts = {r: int(np.asarray(t)[0]) for r, t in first_tok.items()}
            results: Dict[int, RequestResult] = {}
            emitted = 0
            for rid, (plen, padded, adm, t_sub, t_adm) in meta.items():
                fin = finished.get(rid, step)
                cols = [firsts[rid]]
                for s_ in range(adm, fin):
                    b = owners[s_].index(rid) if rid in owners[s_] else -1
                    if b >= 0:
                        cols.append(int(decoded[s_, b]))
                results[rid] = RequestResult(
                    rid=rid, prompt_len=plen, padded_len=padded,
                    tokens=np.asarray(cols, np.int32), admit_step=adm,
                    finish_step=fin, finished=rid in finished,
                    t_submit=t_sub, t_admit=t_adm)
                emitted += len(cols)
        # Stats come off the per-run registry where the accumulator was a
        # counter (same integer arithmetic as the old hand-rolled dicts, so
        # the public schema AND values are unchanged).
        real_rows, padded_rows = c_real.value, c_padded.value
        pad_frac = (1.0 - real_rows / padded_rows) if padded_rows else 0.0
        bucket_hits = {int(dict(m.labels)["edge"]): m.value
                       for m in reg.metrics()
                       if m.name == "engine_bucket_hits"}
        # ``t_decode`` is the whole loop, admissions included.
        tokens_per_s = emitted / max(t_decode, 1e-9)
        reg.counter("engine_steps").inc(step)
        reg.counter("engine_tokens_emitted").inc(emitted)
        reg.gauge("engine_tokens_per_s").set(tokens_per_s)
        reg.gauge("engine_pad_fraction").set(pad_frac)
        if obs_metrics.metrics_enabled():
            obs_metrics.get_registry().merge(reg)
        return {
            "results": results,
            "steps": step,
            "drained": drained,
            "retries": self.retries,
            "t_admit_s": t_admit_s,
            "t_decode_s": t_decode,
            "tokens_emitted": emitted,
            "tokens_per_s": tokens_per_s,
            "bucket_hits": dict(sorted(bucket_hits.items())),
            "pad_fraction": pad_frac,
            "step_ready_s": ready,
            "step_dispatch_s": dispatch_acc,
            "queued_left": len(self._queue),
            "residual_active": get_residual_corrector() is not None,
        }
