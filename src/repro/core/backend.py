"""Unified approximate-arithmetic backend registry.

The paper's core claim is one pipelined log-domain unit reused across
multi-kernel applications; this module is the software analogue: every
``qmatmul`` / ``approx_div`` call site routes through *one* dispatch
layer instead of hand-picking between the jnp scan formulation, the
Pallas TPU kernel, and the elementwise float ops.

A backend bundles four entry points:

  * ``matmul(x2, w2, scheme, *, chunk, bias, activation, residual,
    epilogue)`` — 2-D ``[M, K] @ [K, N]`` approximate contraction in
    f32, with an optional fused output-tile epilogue drawn from the
    **epilogue menu** (see :class:`Epilogue`): any composition of
    ``{bias, activation, residual-add, rms-normalize, softmax-combine}``
    so a whole transformer block tail
    ``norm(activation(x @ w + b) + residual)`` executes in one pass;
  * ``div(a, b, scheme)`` — elementwise approximate divide;
  * ``softmax_div(e, scheme, *, floor)`` — softmax combine:
    ``e / max(sum(e, -1), floor)``, denominator reduction + RAPID divide
    fused in one pass;
  * ``rms_div(x, eps, scheme)`` — rms normalize:
    ``x / sqrt(mean(x^2, -1) + eps)``, likewise fused.

Built-in backends:

  * ``jnp``              — chunked pure-jnp scan (partitioner-visible;
                           the oracle the kernels are tested against);
  * ``pallas``           — the TPU kernels in ``repro.kernels`` (VMEM
                           tiled; ``log_matmul`` for matmuls,
                           ``fused_div`` for the divider family);
  * ``pallas-interpret`` — same kernels under the Pallas interpreter
                           (CPU debugging / CI parity checks).

The divider family — and the epilogue menu's normalization stages —
share canonical semantics with the fused kernels
(``repro.kernels.fused_div.ref``): the denominator reduction runs over
the 128-lane-padded row on every backend, so ``jnp`` and
``pallas-interpret`` agree bit-for-bit.

Selection (``resolve_backend_name``) is one function with a strict
precedence: explicit argument > ``RAPID_BACKEND`` env var > process
default (``set_default_backend``) > hardware autodetect (``pallas`` on
TPU, ``jnp`` elsewhere).  ``None``/"auto" at a call site means "defer to
the next level down".

Manual-mesh awareness: the Pallas kernels are per-device, so on a
multi-device process the hardware level answers differently depending on
*where* the call is traced — ``jnp`` from the global (pjit/GSPMD) view,
but ``pallas`` inside a ``shard_map`` body, where shapes are already
per-shard and the kernel is legal (``repro.compat.in_shard_map``).
Because that answer depends on trace context, :func:`pin_backends`
collapses the arg/env/process-default levels eagerly but pins the
hardware level as the :data:`AUTO_HW` sentinel exactly when it is
context-dependent (multi-device TPU); ``AUTO_HW`` re-consults only the
memoized hardware probe + the axis-env at dispatch time, never the env
var, so a pinned config still cannot be flipped by post-build env
changes.

Per-site overrides: model code never picks a literal backend — it asks
``ApproxConfig.backend_for(site)`` (sites: ``mlp`` / ``attn_proj`` /
``logits`` / ``norm`` / ``softmax``), each of which resolves through the
same selection function.  One model can therefore mix, e.g., pallas
fused-tail MLP matmuls with partitioner-visible jnp logits;
:func:`pin_backends` collapses every site to a concrete registry name
once at engine/trainstep build time.
"""
from __future__ import annotations

import functools
import os
from dataclasses import dataclass, field, replace as dataclass_replace
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import compat
from repro.core import float_approx as fa
from repro.kernels.fused_div import ref as fdref
from repro.kernels.spec import as_kernel_spec, resolve_spec

__all__ = [
    "Backend",
    "ENV_VAR",
    "AUTO_HW",
    "ACTIVATIONS",
    "SOFTMAX_FLOOR",
    "Epilogue",
    "normalize_activation",
    "as_epilogue",
    "apply_epilogue",
    "apply_epilogue_tile",
    "register_backend",
    "get_backend",
    "available_backends",
    "registered_sites",
    "dispatch_signature",
    "resolve_backend_name",
    "resolve_site_device_local",
    "pin_backend_name",
    "set_default_backend",
    "invalidate_device_probe",
    "pin_backends",
    "matmul",
    "div",
    "softmax_div",
    "rms_div",
    "decode_attn",
]

ENV_VAR = "RAPID_BACKEND"

#: Pinned-but-context-dependent hardware selection: "resolve from the
#: memoized device probe + the trace context (in/out of shard_map) at
#: dispatch time".  pin_backends emits this exactly when the hardware
#: answer differs between the global and the device-local view
#: (multi-device TPU); unlike "auto" it never re-reads the env var or
#: the process default.
AUTO_HW = "auto-hw"

# Default softmax-combine denominator floor (re-exported from the fused
# kernels' canonical-semantics module).
SOFTMAX_FLOOR = fdref.SOFTMAX_FLOOR

# Fused-epilogue activations.  Keep this table tiny and shared: the Pallas
# kernel applies the *same* jnp function inside the kernel body.  "gelu"
# is jax's default tanh approximation (matches the model zoo's historic
# numerics); "gelu_erf" is the exact erf form, which is additionally
# *bit-stable* across compilation contexts — the tanh approximation's
# mul/add chain gets FMA-fused differently inside vs outside a
# pallas_call, so cross-backend bit-parity checks must use gelu_erf.
ACTIVATIONS: Dict[str, Callable] = {
    "relu": jax.nn.relu,
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "gelu_erf": functools.partial(jax.nn.gelu, approximate=False),
    "tanh": jnp.tanh,
}


def normalize_activation(activation: Optional[str]) -> Optional[str]:
    """Canonicalize an epilogue activation name (None for identity).

    The single validation point for every entry into the fused epilogue
    (ops.qmatmul, backend.apply_epilogue, the Pallas wrapper) so typos
    raise the same clear error everywhere.
    """
    if activation in (None, "none", "linear"):
        return None
    if activation not in ACTIVATIONS:
        raise KeyError(
            f"unknown activation {activation!r}; have {tuple(ACTIVATIONS)}")
    return activation


def apply_epilogue(out: jnp.ndarray, bias, activation: Optional[str]):
    """``activation(out + bias)`` — the shared fused-epilogue semantics.

    ``bias`` is ``None`` or a 1-D ``[N]`` vector broadcast over rows;
    ``activation`` is ``None``/"none" or a key of :data:`ACTIVATIONS`.
    """
    activation = normalize_activation(activation)
    if bias is not None:
        out = out + bias[None, :]
    if activation is not None:
        out = ACTIVATIONS[activation](out)
    return out


# --------------------------------------------------------------------------
# Epilogue menu: composable output-tile epilogues
# --------------------------------------------------------------------------

#: Normalization stages the epilogue menu offers.  Both reuse the fused
#: divider kernels' canonical lane-padded denominator semantics
#: (``repro.kernels.fused_div.ref``).
EPILOGUE_NORMS = ("rms", "softmax")


@dataclass(frozen=True)
class Epilogue:
    """What to apply to the output tile on its last K visit.

    The full menu is ``norm(activation(out + bias) + residual)``; every
    stage is optional.  Presence of the *bias* and *residual* stages is
    decided by whether the corresponding operand is passed to the matmul
    — this spec carries the purely-static part (hashable, so it can ride
    jit static args and ``custom_vjp`` nondiff positions):

      * ``activation``   — key of :data:`ACTIVATIONS` (None = identity);
      * ``norm``         — None, "rms" (``z / sqrt(mean(z^2, -1) + eps)``)
                           or "softmax" (``z / max(sum(z, -1), floor)``);
      * ``div_scheme``   — RAPID divider scheme for the norm stage's
                           divide (None = exact IEEE divide);
      * ``eps`` / ``floor`` — the rms / softmax denominator constants;
      * ``keep_prenorm`` — also return the value *before* the norm stage
                           (the residual stream a pre-norm transformer
                           block must carry forward), as ``(tail, pre)``.

    The norm stages reduce over the output's last dim, so they require a
    2-D weight (``qmatmul`` enforces this) and — on the Pallas backend —
    an output tile spanning the full lane-padded row.
    """

    activation: Optional[str] = None
    norm: Optional[str] = None
    div_scheme: Optional[str] = None
    eps: float = 1e-6
    floor: float = SOFTMAX_FLOOR
    keep_prenorm: bool = False

    @property
    def wants_norm_lut(self) -> bool:
        """Whether the norm stage needs an on-device divider LUT."""
        return self.norm is not None and self.div_scheme is not None

    @property
    def is_identity(self) -> bool:
        return self.activation is None and self.norm is None


def as_epilogue(epilogue: Optional[Epilogue],
                activation: Optional[str] = None) -> Epilogue:
    """Canonicalize/validate the (epilogue, activation) call-site pair.

    ``activation=`` is the historical sugar for the activation-only
    epilogue; passing both it and a full spec is ambiguous and raises.
    """
    if epilogue is None:
        return Epilogue(activation=normalize_activation(activation))
    if not isinstance(epilogue, Epilogue):
        raise TypeError(f"epilogue must be an Epilogue, got {epilogue!r}")
    if normalize_activation(activation) is not None:
        raise ValueError("pass the activation inside the Epilogue spec, "
                         "not alongside it")
    if epilogue.norm is not None and epilogue.norm not in EPILOGUE_NORMS:
        raise KeyError(f"unknown epilogue norm {epilogue.norm!r}; "
                       f"have {EPILOGUE_NORMS}")
    if epilogue.keep_prenorm and epilogue.norm is None:
        raise ValueError("keep_prenorm without a norm stage is meaningless")
    act = normalize_activation(epilogue.activation)
    if act != epilogue.activation:
        epilogue = dataclass_replace(epilogue, activation=act)
    return epilogue


def apply_epilogue_tile(z, bias, residual, ep: Epilogue, *, n: int,
                        div_lut=None):
    """Canonical epilogue-menu semantics on one lane-padded row slab.

    ``z`` is ``[rows, n_pad]`` f32 with the real width ``n`` zero-padded
    to a multiple of ``fused_div.ref.LANE``; ``bias`` (``[n_pad]``) and
    ``residual`` (``[rows, n_pad]``) are zero-padded the same way.  Used
    *verbatim* by the jnp oracle and the Pallas kernel epilogue, so the
    two backends agree bit-for-bit by construction.

    Pad-lane invariant: every elementwise stage maps exact zeros to
    exact zeros (zero bias/residual pads; every :data:`ACTIVATIONS`
    entry satisfies ``f(0) == 0``), so the canonical lane-padded
    denominator reductions (``ref.softmax_denom`` / ``ref.rms_denom``)
    only ever see inert zeros in the pad lanes.  A future activation
    with ``f(0) != 0`` would need a pad mask here.

    Compilation-context note: compositions where a mul-tailed activation
    (silu/gelu — their last op is a multiply) feeds the residual add are
    rewritten by XLA when the whole chain sits in one compiled module
    (the divide inside the sigmoid is reformulated against the trailing
    add; optimization barriers do not block it).  Bit-parity for those
    compositions therefore holds between two *compiled* executions —
    which is how models always run — not between eager jnp and a jitted
    kernel; the parity sweep jits the oracle side accordingly.
    """
    if bias is not None:
        z = z + bias[None, :]
    if ep.activation is not None:
        z = ACTIVATIONS[ep.activation](z)
    if residual is not None:
        z = z + residual
    pre = z
    if ep.norm == "softmax":
        denom = fdref.softmax_denom(z, ep.floor)
        z = (fa.log_div_f32(z, denom, div_lut)
             if ep.div_scheme is not None else z / denom)
    elif ep.norm == "rms":
        denom = fdref.rms_denom(z, n, ep.eps)
        z = (fa.log_div_f32(z, denom, div_lut)
             if ep.div_scheme is not None else z / denom)
    return (z, pre) if ep.keep_prenorm else z


# --------------------------------------------------------------------------
# jnp scan formulation (moved here from core/ops.py so the registry owns
# every execution path; ops.py re-exports it for the kernels' oracles).
# --------------------------------------------------------------------------

def log_matmul_scan(
    x: jnp.ndarray, w: jnp.ndarray, lut: jnp.ndarray, chunk: int
) -> jnp.ndarray:
    """RAPID matmul x[M,K] @ w[K,N] via K-chunked log-domain products.

    ``chunk=1`` degenerates to a strictly sequential left-to-right
    accumulation — the same association order as the Pallas kernel's
    rank-1 slab loop, which the bit-exactness tests rely on.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    chunk = min(chunk, k)
    pad = (-k) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
        w = jnp.pad(w, ((0, pad), (0, 0)))
    steps = (k + pad) // chunk
    xs = x.reshape(m, steps, chunk).transpose(1, 0, 2)  # [steps, M, C]
    ws = w.reshape(steps, chunk, n)  # [steps, C, N]

    def body(acc, operands):
        xc, wc = operands
        prod = fa.log_mul_f32(xc[:, :, None], wc[None, :, :], lut)  # [M,C,N]
        return acc + prod.sum(axis=1), None

    acc0 = jnp.zeros((m, n), jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, (xs, ws))
    return acc


def _finish_epilogue_jnp(out, bias, residual, ep: Epilogue):
    """Apply the epilogue menu to an unpadded [M, N] jnp matmul output.

    Elementwise-only epilogues run unpadded (bit-equal to the padded
    form lane by lane); norm epilogues lane-pad first so the canonical
    tile semantics — shared verbatim with the kernel — see the same
    reduction operand width, then slice the pads back off.
    """
    if ep.norm is None:
        return apply_epilogue_tile(out, bias, residual, ep, n=out.shape[-1])
    n = out.shape[-1]
    div_lut = (fa.div_lut_device(ep.div_scheme)
               if ep.div_scheme is not None else None)
    res = apply_epilogue_tile(
        fdref.pad_lanes(out),
        None if bias is None else fdref.pad_lanes(bias),
        None if residual is None else fdref.pad_lanes(residual),
        ep, n=n, div_lut=div_lut)
    if ep.keep_prenorm:
        return res[0][:, :n], res[1][:, :n]
    return res[:, :n]


def _matmul_jnp(x2, w2, scheme, *, chunk=64, bias=None, activation=None,
                residual=None, epilogue: Optional[Epilogue] = None,
                spec=None):
    # spec is the kernel families' KernelSpec; the scan formulation has
    # no block/pipeline geometry to configure, so it is accepted (the
    # dispatchers pass one spec to every backend uniformly) and ignored.
    del spec
    ep = as_epilogue(epilogue, activation)
    lut = fa.mul_lut_device(scheme)
    out = log_matmul_scan(x2, w2, lut, chunk)
    return _finish_epilogue_jnp(out, bias, residual, ep)


def _matmul_pallas(x2, w2, scheme, *, chunk=64, bias=None, activation=None,
                   residual=None, epilogue: Optional[Epilogue] = None,
                   spec=None, interpret: Optional[bool] = None):
    # chunk is a jnp-path tuning knob; the kernel has its own block
    # sizes, pinned here at the dispatch layer through the resolve_spec
    # choke point (explicit spec field > tuning cache > heuristic); the
    # wrapper's own resolve is then an idempotent no-op.
    del chunk
    from repro.kernels.log_matmul.ops import log_matmul

    ks = as_kernel_spec(spec)
    ep = as_epilogue(epilogue if epilogue is not None else ks.epilogue,
                     activation)
    ks = resolve_spec("log_matmul", (x2.shape[0], w2.shape[1], x2.shape[1]),
                      ks, scheme=scheme or ks.scheme or "rapid10",
                      epilogue=ep)
    return log_matmul(x2, w2, scheme, bias=bias, activation=activation,
                      residual=residual, epilogue=epilogue, spec=ks,
                      interpret=interpret)


def _matmul_pallas_interpret(x2, w2, scheme, **kw):
    kw["interpret"] = True
    return _matmul_pallas(x2, w2, scheme, **kw)


# --------------------------------------------------------------------------
# divider family: elementwise div, fused softmax combine, fused rms
# normalize.  The jnp implementations ARE the canonical semantics (the
# fused kernels evaluate the same expressions on their VMEM tiles).
# --------------------------------------------------------------------------

def _softmax_div_jnp(e, scheme, *, floor=SOFTMAX_FLOOR, spec=None):
    """e / max(sum(e, -1), floor) with the RAPID divider.  f32 in/out."""
    del spec
    return fdref.softmax_div_ref(e, fa.div_lut_device(scheme), floor)


def _rms_div_jnp(x, eps, scheme, *, spec=None):
    """x / sqrt(mean(x^2, -1) + eps) with the RAPID divider.  f32."""
    del spec
    return fdref.rms_div_ref(x, fa.div_lut_device(scheme), eps)


def _div_jnp(a, b, scheme, *, spec=None):
    """Elementwise RAPID divide (the LUT bit-twiddle, no kernel)."""
    del spec
    return fa.approx_div(a, b, scheme)


def _div_pallas(a, b, scheme, *, spec=None,
                interpret: Optional[bool] = None):
    from repro.kernels.fused_div.ops import fused_elementwise_div

    return fused_elementwise_div(a, b, scheme, spec=spec,
                                 interpret=interpret)


def _div_pallas_interpret(a, b, scheme, *, spec=None):
    return _div_pallas(a, b, scheme, spec=spec, interpret=True)


def _row_resolved(family, x, scheme, spec):
    """Pin a fused-divider row spec at the dispatch layer (idempotent
    with the wrapper's own resolve_spec pass)."""
    ks = as_kernel_spec(spec)
    rows = 1
    for d in x.shape[:-1]:
        rows *= int(d)
    return resolve_spec(family, (rows, x.shape[-1]), ks,
                        scheme=scheme or ks.scheme or "rapid9")


def _softmax_div_pallas(e, scheme, *, floor=SOFTMAX_FLOOR, spec=None,
                        interpret: Optional[bool] = None):
    from repro.kernels.fused_div.ops import fused_softmax_div

    return fused_softmax_div(
        e, scheme, floor=floor,
        spec=_row_resolved("fused_softmax", e, scheme, spec),
        interpret=interpret)


def _softmax_div_pallas_interpret(e, scheme, *, floor=SOFTMAX_FLOOR,
                                  spec=None):
    return _softmax_div_pallas(e, scheme, floor=floor, spec=spec,
                               interpret=True)


def _rms_div_pallas(x, eps, scheme, *, spec=None,
                    interpret: Optional[bool] = None):
    from repro.kernels.fused_div.ops import fused_rms_div

    return fused_rms_div(x, eps, scheme,
                         spec=_row_resolved("fused_rms", x, scheme, spec),
                         interpret=interpret)


def _rms_div_pallas_interpret(x, eps, scheme, *, spec=None):
    return _rms_div_pallas(x, eps, scheme, spec=spec, interpret=True)


# --------------------------------------------------------------------------
# decode-attention family: one fused flash-decode step (score matmul,
# online softmax stats, value matmul, floored RAPID combine divide) —
# the flagship consumer of the pipelined kernels.  The jnp impl is the
# canonical semantics (the kernel reproduces it to f32 tolerance; the
# contractions are exact on both paths, only the combine divide is
# approximate).
# --------------------------------------------------------------------------

def _decode_attn_jnp(qf, k_cache, v_cache, slot_positions, pos, window,
                     scheme, *, floor=SOFTMAX_FLOOR, spec=None):
    del spec
    from repro.kernels.flash_attn.ref import decode_attn_ref

    return decode_attn_ref(qf, k_cache, v_cache, slot_positions, pos,
                           window, scheme, floor=floor)


def _decode_attn_pallas(qf, k_cache, v_cache, slot_positions, pos, window,
                        scheme, *, floor=SOFTMAX_FLOOR, spec=None,
                        interpret: Optional[bool] = None):
    from repro.kernels.flash_attn.ops import flash_decode_attn

    b, kv, g, hd = qf.shape
    ks = resolve_spec("flash_attn", (b * kv, k_cache.shape[1], g, hd),
                      as_kernel_spec(spec), scheme=scheme)
    return flash_decode_attn(qf, k_cache, v_cache, slot_positions, pos,
                             window, scheme, floor=floor, spec=ks,
                             interpret=interpret)


def _decode_attn_pallas_interpret(qf, k_cache, v_cache, slot_positions,
                                  pos, window, scheme, *,
                                  floor=SOFTMAX_FLOOR, spec=None):
    return _decode_attn_pallas(qf, k_cache, v_cache, slot_positions, pos,
                               window, scheme, floor=floor, spec=spec,
                               interpret=True)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class Backend:
    """One named execution path for the approximate ops."""

    name: str
    matmul: Callable
    div: Callable = field(default=_div_jnp)
    softmax_div: Callable = field(default=_softmax_div_jnp)
    rms_div: Callable = field(default=_rms_div_jnp)
    decode_attn: Callable = field(default=_decode_attn_jnp)
    description: str = ""


_REGISTRY: Dict[str, Backend] = {}
_DEFAULT: Optional[str] = None


def register_backend(backend: Backend, *, overwrite: bool = False) -> Backend:
    """Add a backend to the registry (third parties included)."""
    if backend.name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {backend.name!r} already registered")
    _REGISTRY[backend.name] = backend
    return backend


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def registered_sites() -> Tuple[str, ...]:
    """Every site name an ApproxConfig backend map can carry.

    The public answer to "what call sites does the registry dispatch?"
    — the dispatch auditor iterates it, and tests that used to reach
    into ``configs.base.BACKEND_SITES`` (or the private ``_REGISTRY``)
    ask here instead.  "default" leads: it is the fallback every other
    site defers to.
    """
    from repro.configs.base import BACKEND_SITES  # local: avoid cycle

    return ("default",) + BACKEND_SITES


def dispatch_signature(name: str) -> Dict[str, str]:
    """family -> implementing ``module:qualname`` for one backend.

    Introspection for the auditor and tests: states *which function*
    each registry family (matmul / div / softmax_div / rms_div) actually
    dispatches to, without reaching into the private registry dict.
    ``name`` resolves through the normal selection precedence, so
    ``dispatch_signature("auto")`` answers for the ambient default.
    """
    b = _REGISTRY[resolve_backend_name(name)]
    return {
        family: f"{fn.__module__}:{fn.__qualname__}"
        for family, fn in (("matmul", b.matmul), ("div", b.div),
                           ("softmax_div", b.softmax_div),
                           ("rms_div", b.rms_div),
                           ("decode_attn", b.decode_attn))
    }


def set_default_backend(name: Optional[str]) -> None:
    """Set (or clear, with ``None``) the process-wide default backend."""
    global _DEFAULT
    if name is not None and name not in _REGISTRY:
        raise KeyError(f"unknown backend {name!r}; have {available_backends()}")
    _DEFAULT = name


@functools.lru_cache(maxsize=1)
def _device_probe() -> Tuple[str, int]:
    """Memoized (platform, n_devices) hardware probe.

    ``resolve_backend_name`` runs on *every* dispatch (each qmatmul/qdiv
    trace), while ``jax.device_count()`` walks the live device list each
    call — so the probe is sampled once per process.  Tests that fake
    the device count must call :func:`invalidate_device_probe` after
    (un)patching.
    """
    return jax.default_backend(), jax.device_count()


def invalidate_device_probe() -> None:
    """Drop the memoized (platform, n_devices) sample (test hook)."""
    _device_probe.cache_clear()


def _autodetect(device_local: Optional[bool] = None) -> str:
    """Hardware default: pallas on TPU wherever the call is device-local.

    The pallas matmul is a per-device kernel, so on a multi-device TPU
    process the answer depends on the trace context: pjit-traced global
    code must give the partitioner the jnp formulation, but a
    ``shard_map`` body already sees per-shard shapes and runs the kernel
    on the local shard (the EP/TP paths in ``models/moe.py``).
    ``device_local=None`` consults the axis environment
    (``compat.in_shard_map``); callers that know their locality (e.g. a
    shard_map body resolving before entering the region) pass it
    explicitly.
    """
    platform, n_devices = _device_probe()
    if platform != "tpu":
        return "jnp"
    if n_devices == 1:
        return "pallas"
    if device_local is None:
        device_local = compat.in_shard_map()
    return "pallas" if device_local else "jnp"


def _collapse_levels(name: Optional[str]) -> Optional[str]:
    """The shared arg > env > process-default precedence walk.

    Returns a concrete registry name, :data:`AUTO_HW` when some level
    explicitly requested the hardware step, or ``None`` when every
    level deferred — the two terminals (:func:`resolve_backend_name` /
    :func:`pin_backend_name`) differ only in what they do next.
    """
    for candidate in (name, os.environ.get(ENV_VAR), _DEFAULT):
        if candidate and candidate != "auto":
            if candidate == AUTO_HW:
                return AUTO_HW
            if candidate not in _REGISTRY:
                raise KeyError(
                    f"unknown backend {candidate!r}; have {available_backends()}")
            return candidate
    return None


def resolve_backend_name(name: Optional[str] = None, *,
                         device_local: Optional[bool] = None) -> str:
    """One selection function for every call site.

    Precedence: explicit ``name`` > ``$RAPID_BACKEND`` > process default
    (:func:`set_default_backend`) > autodetect (pallas wherever the call
    is device-local on TPU, else jnp).  ``None`` and "auto" defer to the
    next level; the :data:`AUTO_HW` sentinel (what :func:`pin_backends`
    pins on multi-device TPU) jumps straight to autodetect — the env/
    default levels were already consulted at pin time.  ``device_local``
    overrides the in-shard_map detection at the hardware level.
    """
    got = AUTO_HW if name == AUTO_HW else _collapse_levels(name)
    if got is None or got == AUTO_HW:
        return _autodetect(device_local)
    return got


def pin_backend_name(name: Optional[str] = None) -> str:
    """Build-time companion of :func:`resolve_backend_name`.

    The arg/env/process-default levels collapse to a concrete registry
    name *now* (so later env changes cannot flip a compiled kernel
    choice), but the hardware level stays pinned as :data:`AUTO_HW`
    exactly when its answer depends on trace context — a multi-device
    TPU process, where global-view sites must resolve to jnp while
    shard_map bodies legally run the pallas kernels per shard.  On CPU
    or a single device the hardware answer is context-free and pins
    concretely, exactly as before.
    """
    got = _collapse_levels(name)
    if got is not None and got != AUTO_HW:
        return got
    platform, n_devices = _device_probe()
    if platform == "tpu" and n_devices > 1:
        return AUTO_HW
    return _autodetect(device_local=False)


def resolve_site_device_local(acfg, site: str):
    """Pin one site of an ApproxConfig from the device-local view.

    The helper model code calls right before building a ``shard_map``
    body: the body's dispatches are per-shard, so the site's backend is
    resolved with ``device_local=True`` (an AUTO_HW / auto entry may
    legally become the pallas kernels on a multi-device process) and
    written back as a concrete name, fixing the body's kernel choice
    before tracing begins.  Explicit names pass through unchanged.
    """
    name = resolve_backend_name(acfg.backend_for(site), device_local=True)
    return acfg.with_backends({site: name})


def get_backend(name: Optional[str] = None) -> Backend:
    """Resolve ``name`` (or the ambient default) to a Backend."""
    return _REGISTRY[resolve_backend_name(name)]


def pin_backends(acfg, override: Optional[str] = None):
    """Collapse an ApproxConfig's site->backend map at build time.

    Every site (plus the default) is resolved through
    :func:`pin_backend_name` exactly once, so engines / train steps
    built from the returned config cannot have env-var changes silently
    flip the compiled kernel choice inside a later trace.  ``override``
    (an explicit registry name) wins at every site.  On a multi-device
    TPU, sites left to hardware autodetect pin as :data:`AUTO_HW` — the
    one selection whose answer legitimately differs per call site
    (jnp under pjit, pallas inside shard_map bodies).
    """
    from repro.configs.base import BACKEND_SITES  # local: avoid cycle

    sites = {
        site: pin_backend_name(override or acfg.backend_for(site))
        for site in ("default",) + BACKEND_SITES
    }
    return dataclass_replace(acfg, backends=sites)


def matmul(x2, w2, scheme, *, backend: Optional[str] = None, **kw):
    """Registry-routed 2-D approximate matmul (see Backend.matmul)."""
    return get_backend(backend).matmul(x2, w2, scheme, **kw)


def div(a, b, scheme, *, backend: Optional[str] = None, **kw):
    """Registry-routed elementwise approximate divide."""
    return get_backend(backend).div(a, b, scheme, **kw)


def softmax_div(e, scheme, *, backend: Optional[str] = None,
                floor: float = SOFTMAX_FLOOR, **kw):
    """Registry-routed fused softmax combine (see Backend.softmax_div)."""
    return get_backend(backend).softmax_div(e, scheme, floor=floor, **kw)


def rms_div(x, eps, scheme, *, backend: Optional[str] = None, **kw):
    """Registry-routed fused rms normalize (see Backend.rms_div)."""
    return get_backend(backend).rms_div(x, eps, scheme, **kw)


def decode_attn(qf, k_cache, v_cache, slot_positions, pos, window, scheme,
                *, backend: Optional[str] = None, **kw):
    """Registry-routed fused decode attention (see Backend.decode_attn)."""
    return get_backend(backend).decode_attn(
        qf, k_cache, v_cache, slot_positions, pos, window, scheme, **kw)


register_backend(Backend(
    "jnp", _matmul_jnp,
    description="chunked jnp scan; GSPMD-partitionable oracle"))
register_backend(Backend(
    "pallas", _matmul_pallas,
    div=_div_pallas,
    softmax_div=_softmax_div_pallas,
    rms_div=_rms_div_pallas,
    decode_attn=_decode_attn_pallas,
    description="Pallas TPU kernels (VMEM tiled, software-pipelined)"))
register_backend(Backend(
    "pallas-interpret", _matmul_pallas_interpret,
    div=_div_pallas_interpret,
    softmax_div=_softmax_div_pallas_interpret,
    rms_div=_rms_div_pallas_interpret,
    decode_attn=_decode_attn_pallas_interpret,
    description="Pallas kernels under the interpreter (CPU debug/CI)"))
