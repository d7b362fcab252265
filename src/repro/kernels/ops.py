"""Public kernel ops: selector-driven, backend-switchable, jit-friendly.

Backends
--------
``pallas``            real Mosaic lowering (TPU runtime)
``pallas_interpret``  kernel body executed in Python on CPU (tests/validation)
``reference``         pure-jnp oracle with identical semantics — used by the
                      multi-pod dry-run (Mosaic cannot lower for the CPU
                      platform) and as the default on CPU hosts; its FLOP and
                      byte counts match the kernel algorithm, which is what
                      the roofline reads.

Selection happens at *trace time* from static shapes via
``repro.core.select_gemm_config`` — the tritonBLAS contract: zero autotuning,
deterministic, memoised.

Fail-soft launch (DESIGN.md §9): selector-driven launches re-validate the
selection before lowering and, on a kernel compile/launch failure, walk a
deterministic fallback ladder — next-ranked candidate, conservative safe
config, reference kernel — each transient-retried and each downgrade
reported through the selection hooks as a ``fallback:<rung>`` source.
Explicitly-passed ``config`` objects are the caller's contract and never
silently swapped: they get the transient retry but not the ladder.  The
ladder answers launch failures only: a tracing or transformation error
(``TypeError``, ``NotImplementedError`` — e.g. differentiating a kernel,
which has no VJP) would fail on every rung alike and propagates.
"""
from __future__ import annotations

import os
import warnings
from typing import Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.dtypes import DTYPE_BYTES
from repro.core.hardware import TPU_V5E, preset_for_device_kind
from repro.core.topology import DegradedModeWarning, HardwareSpec
from repro.core.latency import EPILOGUE_NONE, Epilogue, TileConfig, cdiv
from repro.core.selector import (Selection, emit_fallback, fallback_ladder,
                                 select_gemm_config, validate_selection)
from repro.kernels import ref
from repro.kernels.flash_attention import (
    flash_attention_pallas,
    select_attention_blocks,
)
from repro.kernels.matmul import matmul_pallas
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.runtime.fault_tolerance import retry

_BACKENDS = ("pallas", "pallas_interpret", "reference")
_backend_override: Optional[str] = None


def set_backend(name: Optional[str]) -> None:
    """Force a kernel backend globally (None -> auto)."""
    global _backend_override
    if name is not None and name not in _BACKENDS:
        raise ValueError(f"backend {name!r} not in {_BACKENDS}")
    _backend_override = name


def get_backend() -> str:
    if _backend_override is not None:
        return _backend_override
    env = os.environ.get("REPRO_KERNEL_BACKEND")
    if env:
        if env not in _BACKENDS:
            raise ValueError(f"REPRO_KERNEL_BACKEND={env!r} not in {_BACKENDS}")
        return env
    return "pallas" if jax.default_backend() == "tpu" else "reference"


# ---------------------------------------------------------------------------
# Default serving hardware.  Call sites that don't pass ``hw`` price their
# selections against this topology; ``launch/serve.py`` points it at a
# calibrated-topology artifact (or its stock-preset fallback when the
# artifact was quarantined).  ``None`` -> the preset of the attached TPU
# (an unknown device kind is an error, never another chip's peaks); off-TPU
# the tpu_v5e preset, the chip selections made on a CPU host are for.
# ---------------------------------------------------------------------------

_hw_override: Optional[HardwareSpec] = None


def set_default_hardware(hw: Optional[HardwareSpec]) -> None:
    """Set the topology used when call sites omit ``hw`` (None -> preset)."""
    global _hw_override
    _hw_override = hw


def get_default_hardware() -> HardwareSpec:
    if _hw_override is not None:
        return _hw_override
    if jax.default_backend() == "tpu":
        return preset_for_device_kind(jax.devices()[0].device_kind)
    return TPU_V5E


# ---------------------------------------------------------------------------
# Launch fault injection (the chaos harness's hook, repro.calib.faults).
# When set, the injector is invoked with the TileConfig about to launch and
# may raise — a transient-marked error exercises the retry path, anything
# else the fallback ladder.  Never set in production.
# ---------------------------------------------------------------------------

_launch_fault_injector: Optional[Callable[[TileConfig], None]] = None


def set_launch_fault_injector(
        fn: Optional[Callable[[TileConfig], None]]
) -> Optional[Callable[[TileConfig], None]]:
    """Install (or clear, with None) the launch fault injector; returns
    the previous injector so tests can restore it."""
    global _launch_fault_injector
    prev = _launch_fault_injector
    _launch_fault_injector = fn
    return prev


# Errors that say the traced program is wrong, not that a tile config
# failed to launch: no fallback rung can fix them, so the ladder re-raises.
_PROGRAM_ERRORS = (TypeError, NotImplementedError)

# Transient-retry policy for kernel launches: short, capped backoff — a
# launch retry protects against injected/driver transients, not outages.
_LAUNCH_RETRIES = 2
_LAUNCH_BASE_DELAY = 0.01
_LAUNCH_MAX_DELAY = 0.1


def _dtype_name(x) -> str:
    return jnp.dtype(x).name


def _pad2(x: jax.Array, m: int, n: int) -> jax.Array:
    pm, pn = (-x.shape[-2]) % m, (-x.shape[-1]) % n
    if pm or pn:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, pm), (0, pn)])
    return x


def normalize_epilogue(
    epilogue: Optional[Union[str, Epilogue]],
    bias, gate, residual,
) -> Epilogue:
    """Accept an Epilogue spec, an activation-name shorthand, or infer the
    spec from which operands were passed; validate operand presence."""
    if isinstance(epilogue, Epilogue):
        ep = epilogue
    elif isinstance(epilogue, str):
        ep = Epilogue(bias=bias is not None, activation=epilogue,
                      residual=residual is not None)
    else:
        ep = Epilogue(bias=bias is not None,
                      activation="swiglu_gate" if gate is not None else None,
                      residual=residual is not None)
    if ep.bias != (bias is not None):
        raise ValueError(f"epilogue {ep} vs bias operand "
                         f"{'present' if bias is not None else 'missing'}")
    if (ep.activation == "swiglu_gate") != (gate is not None):
        raise ValueError(f"epilogue {ep} vs gate operand "
                         f"{'present' if gate is not None else 'missing'}")
    if ep.residual != (residual is not None):
        raise ValueError(f"epilogue {ep} vs residual operand "
                         f"{'present' if residual is not None else 'missing'}")
    return ep


def _model_dtype_name(dt) -> str:
    """The dtype name handed to the cost model — epilogue write bytes must be
    priced in the TRUE out_dtype (bf16 halves them); fall back to f32 only
    for dtypes the model has no byte width for."""
    name = _dtype_name(dt)
    return name if name in DTYPE_BYTES else "float32"


def matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    out_dtype=None,
    hw: Optional[HardwareSpec] = None,
    config: Optional[TileConfig] = None,
    backend: Optional[str] = None,
    epilogue: Optional[Union[str, Epilogue]] = None,
    bias: Optional[jax.Array] = None,
    gate: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
) -> jax.Array:
    """Selector-driven fused GEMM: ``epilogue(a @ b)``.

    a: (..., M, K) [leading dims folded], b: (K, N).  Epilogue operands:
    bias (N,), gate/residual (..., M, N) matching a's leading dims.
    ``epilogue`` may be an :class:`Epilogue`, an activation name shorthand
    ("gelu" | "silu" | "swiglu_gate"), or omitted (inferred from operands).

    The analytical selection uses the *local* (per-shard) static shapes and
    the fused epilogue traffic, so calling this under shard_map gives
    per-chip-optimal tiles — the intended deployment (see
    ``nn.layers.dense``, which puts that boundary around every layer GEMM
    when a mesh is installed).

    ``config`` (and selections made against multi-core topologies) may
    carry ``TileConfig.schedule``: ``"data_parallel"`` or ``"stream_k"``.
    The schedule is a *pricing* distinction of the occupancy-aware wave
    model (DESIGN.md §2); on the TPU backend both lower to the same
    in-kernel split-K grid (`kernels.matmul` module docstring), so passing
    a stream_k selection here is valid and numerically identical.
    """
    be = backend or get_backend()
    hw = hw if hw is not None else get_default_hardware()
    out_dtype = out_dtype or a.dtype
    ep = normalize_epilogue(epilogue, bias, gate, residual)
    lead = a.shape[:-2] if a.ndim > 2 else ()
    M = 1
    for s in (*lead, a.shape[-2]):
        M *= s
    K, N = b.shape
    a2 = a.reshape(M, K)
    gate2 = gate.reshape(M, N) if gate is not None else None
    res2 = residual.reshape(M, N) if residual is not None else None

    def _reference() -> jax.Array:
        out = ref.matmul_ref(a2, b, out_dtype=out_dtype, epilogue=ep,
                             bias=bias, gate=gate2, residual=res2)
        return out.reshape(*lead, a.shape[-2], N) if lead else out

    if be == "reference":
        return _reference()

    selected: Optional[Selection] = None
    if config is None:
        selected = select_gemm_config(M, N, K,
                                      in_dtype=_dtype_name(a.dtype),
                                      out_dtype=_model_dtype_name(out_dtype),
                                      epilogue=ep,
                                      hw=hw)
        config = selected.config
    interpret = be == "pallas_interpret"

    def _launch(cfg: TileConfig) -> jax.Array:
        if _launch_fault_injector is not None:
            _launch_fault_injector(cfg)
        sk = cfg.split_k
        a_p = _pad2(a2, cfg.bm, cfg.bk * sk)
        b_p = _pad2(b, cfg.bk * sk, cfg.bn)
        kw = {}
        if ep.bias:
            kw["bias"] = _pad2(bias.reshape(1, N), 1, cfg.bn)
        if gate2 is not None:
            kw["gate"] = _pad2(gate2, cfg.bm, cfg.bn)
        if res2 is not None:
            kw["residual"] = _pad2(res2, cfg.bm, cfg.bn)
        out = matmul_pallas(a_p, b_p, cfg, out_dtype=out_dtype, epilogue=ep,
                            interpret=interpret, **kw)
        out = out[:M, :N]
        return out.reshape(*lead, a.shape[-2], N) if lead else out

    def _on_retry(attempt: int, e: Exception) -> None:
        obs_metrics.inc("launch_retries")
        obs_trace.event("launch_retry", cat="fault", track="launch",
                        args={"attempt": attempt, "error": repr(e),
                              "shape": [M, N, K]})

    def _try(cfg: TileConfig) -> jax.Array:
        return retry(_launch, cfg, retries=_LAUNCH_RETRIES,
                     base_delay=_LAUNCH_BASE_DELAY,
                     max_delay=_LAUNCH_MAX_DELAY,
                     on_retry=_on_retry)

    if selected is None:
        # Explicit config: the caller's contract.  Transient-retry the
        # launch, but never silently substitute a different config —
        # deterministic failures propagate.
        return _try(config)

    # Selector-driven launch: re-validate before lowering, then walk the
    # deterministic fallback ladder on any launch failure (DESIGN.md §9).
    p = selected.problem
    reason = validate_selection(p, config, hw)
    first_err: Optional[Exception] = None
    if reason is None:
        try:
            return _try(config)
        except _PROGRAM_ERRORS:
            raise
        except Exception as e:                      # noqa: BLE001
            first_err = e
            reason = f"launch failed: {e!r}"
    obs_metrics.inc("launch_validation_failures")
    obs_trace.event("selection_rejected", cat="fault", track="launch",
                    args={"shape": [M, N, K], "reason": reason})
    warnings.warn(
        f"selected config {config} rejected ({reason}); "
        f"walking fallback ladder", DegradedModeWarning, stacklevel=2)
    for sel_f, rung in fallback_ladder(p, hw, config):
        if validate_selection(p, sel_f.config, hw) is not None:
            continue
        obs_metrics.inc("fallback_rungs", labels={"rung": rung})
        obs_trace.event("fallback_rung", cat="fault", track="launch",
                        args={"shape": [M, N, K], "rung": rung})
        emit_fallback(sel_f, rung)
        try:
            return _try(sel_f.config)
        except _PROGRAM_ERRORS:
            raise
        except Exception as e:                      # noqa: BLE001
            first_err = first_err or e
            continue
    # Every tiled rung failed — the reference oracle is semantically
    # identical and cannot mis-tile; report it as the final rung.
    obs_metrics.inc("fallback_rungs", labels={"rung": "reference"})
    obs_trace.event("fallback_rung", cat="fault", track="launch",
                    args={"shape": [M, N, K], "rung": "reference"})
    emit_fallback(selected, "reference")
    warnings.warn(
        f"all tiled fallbacks failed for {p.M}x{p.N}x{p.K} "
        f"(first error: {first_err!r}); serving reference kernel",
        DegradedModeWarning, stacklevel=2)
    return _reference()


def expert_matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    out_dtype=None,
    hw: Optional[HardwareSpec] = None,
    backend: Optional[str] = None,
    epilogue: Optional[Union[str, Epilogue]] = None,
    bias: Optional[jax.Array] = None,
    gate: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
) -> jax.Array:
    """Grouped GEMM with per-group weights: x (E, M, K) @ w (E, K, N) ->
    (E, M, N), with the same fused epilogue as :func:`matmul`.

    This is exactly the paper's "batched or grouped GEMM dimensions" case
    (§II-A): the selector prices the per-expert (M, K, N) contraction once
    and every expert reuses the config.  Epilogue operands carry the leading
    E dim: bias (E, N), gate/residual (E, M, N).
    """
    be = backend or get_backend()
    hw = hw if hw is not None else get_default_hardware()
    out_dtype = out_dtype or x.dtype
    ep = normalize_epilogue(epilogue, bias, gate, residual)

    if be == "reference":
        acc = jnp.einsum("emk,ekn->emn", x, w,
                         preferred_element_type=jnp.float32)
        bias_b = bias[:, None, :] if bias is not None else None
        acc = ref.apply_epilogue_ref(acc, ep, bias=bias_b, gate=gate,
                                     residual=residual)
        return acc.astype(out_dtype)

    extras = []
    if ep.bias:
        extras.append(bias)
    if ep.activation == "swiglu_gate":
        extras.append(gate)
    if ep.residual:
        extras.append(residual)

    def one(xi, wi, *ex):
        it = iter(ex)
        kw = {}
        if ep.bias:
            kw["bias"] = next(it)
        if ep.activation == "swiglu_gate":
            kw["gate"] = next(it)
        if ep.residual:
            kw["residual"] = next(it)
        return matmul(xi, wi, out_dtype=out_dtype, hw=hw, backend=be,
                      epilogue=ep, **kw)

    return jax.vmap(one)(x, w, *extras)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    hw: Optional[HardwareSpec] = None,
    blocks: Optional[Tuple[int, int]] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Selector-driven attention. q: (B,H,Sq,d), k/v: (B,Hkv,Skv,d)."""
    be = backend or get_backend()
    hw = hw if hw is not None else get_default_hardware()
    if be == "reference":
        return ref.attention_ref(q, k, v, causal=causal, scale=scale)

    B, H, Sq, d = q.shape
    _, Hkv, Skv, _ = k.shape
    if blocks is None:
        blocks = select_attention_blocks(
            Sq, Skv, d, in_dtype=_dtype_name(q.dtype), hw=hw, causal=causal)
    bq, bkv = blocks
    bq, bkv = min(bq, max(128, Sq)), min(bkv, max(128, Skv))
    q_p = jnp.pad(q, ((0, 0), (0, 0), (0, (-Sq) % bq), (0, 0)))
    k_p = jnp.pad(k, ((0, 0), (0, 0), (0, (-Skv) % bkv), (0, 0)))
    v_p = jnp.pad(v, ((0, 0), (0, 0), (0, (-Skv) % bkv), (0, 0)))
    out = flash_attention_pallas(
        q_p, k_p, v_p, block_q=bq, block_kv=bkv, causal=causal, scale=scale,
        q_len=Sq, kv_len=Skv, interpret=(be == "pallas_interpret"))
    return out[:, :, :Sq, :]
