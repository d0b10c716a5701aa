"""Layer-aware quantization policy with pluggable kernel backends.

The paper's central finding is that quantization tolerance is component- AND
layer-dependent: embeddings and the lm-head stay fp, the dx gradient path
must stay real-valued, and first/last blocks are more sensitive than the
middle of the stack (Bondarenko et al. 2021 show per-sublayer activation
ranges differ sharply).  A :class:`QuantPolicy` makes that first-class:

* ordered pattern **rules** map a *layer role* (``attn_qkv``, ``mlp_down``,
  ``block[0:2].*`` ...) to a :class:`~repro.core.qconfig.QuantRecipe` (or fp)
  plus a **kernel backend**;
* every weight-bearing matmul in the model zoo calls
  ``policy.linear(ctx, x, w)`` where the :class:`LinearCtx` carries the role,
  the (possibly traced) layer index, and an optional PRNG key;
* backends are looked up in a registry: ``"fake_quant"`` is the reference
  error-injection einsum (paper methodology), ``"int8_pallas"`` runs the real
  W8A8 MXU kernel for supported specs and silently falls back to the
  reference path otherwise.

``QuantPolicy.from_recipe(recipe)`` reproduces the legacy single-recipe
behaviour exactly (block linears quantized; embed / lm-head / router /
patch-adapter fp), so existing presets migrate mechanically.

Layer indices inside ``jax.lax.scan`` over the stacked block params are
traced values; when a policy is depth-sensitive the dispatch groups layers
into equivalence classes and selects the class with ``jax.lax.switch`` -- a
depth-insensitive policy (every ``from_recipe`` policy) keeps the exact
single-branch HLO of the legacy path.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as _np

from repro.core.qadam import QState
from repro.core.qconfig import (Granularity, QuantRecipe, QuantSpec,
                                RoundMode, get_recipe)
from repro.core.qlinear import (int8_backend_supported, int8_bwd_supported,
                                int8_decode_attn_supported,
                                int8_quantized_linear, quantized_linear)
from repro.core.quantizer import fake_quant, maybe_fake_quant

# Layer roles understood by the model zoo.  ``embed`` / ``lm_head`` govern the
# (weight-only) quantization of the embedding table and output head;
# ``patch_proj`` / ``frame_proj`` are the VLM / audio input adapters;
# ``shared_proj`` is the zamba2 shared-block down-projection.  ``kv_cache``
# governs the *storage* precision of the decode KV cache (int8 payload +
# per-head-per-position scales, dequant-on-read) -- fp unless a rule names it.
ROLES = ("embed", "lm_head", "attn_qkv", "attn_out", "mlp_up", "mlp_down",
         "router", "ssm_in", "ssm_out", "shared_proj", "frame_proj",
         "patch_proj", "kv_cache")


# ---------------------------------------------------------------------------
# Kernel backend registry
# ---------------------------------------------------------------------------

class KernelBackend(NamedTuple):
    """A quantized-matmul implementation.

    ``fn(x, w, recipe, key) -> y`` computes the forward (and owns its custom
    VJP); ``supports(recipe)`` gates eligibility -- unsupported recipes fall
    back to the ``fake_quant`` reference automatically.  ``bwd_supports``
    reports whether the backend's backward also runs real quantized compute
    for the recipe (capability metadata -- the backend's own vjp is expected
    to apply the same predicate and degrade gracefully on its own).
    ``decode_attn_supports(kv_spec)`` reports whether the backend ships
    attention kernels that consume a KV cache stored under that spec
    *directly* (int8 payload + scale sidecars, no fp materialization) --
    the serving decode/prefill hot path dispatches on it.
    """
    fn: Callable
    supports: Callable
    bwd_supports: Callable = lambda recipe: False
    decode_attn_supports: Callable = lambda spec: False


KERNEL_BACKENDS: Dict[str, KernelBackend] = {}


def register_backend(name: str, fn: Callable,
                     supports: Callable = lambda recipe: True,
                     bwd_supports: Callable = lambda recipe: False,
                     decode_attn_supports: Callable = lambda spec: False,
                     ) -> None:
    KERNEL_BACKENDS[name] = KernelBackend(fn, supports, bwd_supports,
                                          decode_attn_supports)


register_backend("fake_quant", quantized_linear)
register_backend("int8_pallas", int8_quantized_linear,
                 supports=int8_backend_supported,
                 bwd_supports=int8_bwd_supported,
                 decode_attn_supports=int8_decode_attn_supported)


def _prepared_int8_ok(recipe: Optional[QuantRecipe], w: QState) -> bool:
    """Can the real-int8 MXU kernel consume this prepared weight directly?
    Needs the full W8A8 contract (symmetric, nearest, unblocked) and a plain
    2-D payload (stacked / per-expert payloads run the dequant matmul)."""
    return (int8_backend_supported(recipe) and w.q.ndim == 2
            and w.q.dtype == jnp.int8)


def _prepared_matmul(resolved: "Resolved", x: jnp.ndarray, w: QState,
                     key) -> jnp.ndarray:
    """Serving path: the weight arrives as a stored integer payload + scales
    (quantized ONCE at engine construction -- see ``repro.infer.prepare``), so
    the trace contains *no* weight quantize step (no absmax reduce, no round).
    Activations still follow the resolved recipe."""
    recipe = resolved.recipe
    a_spec = recipe.acts if recipe is not None else None
    if (resolved.backend == "int8_pallas" and a_spec is not None
            and _prepared_int8_ok(recipe, w)):
        from repro.kernels.ops import int8_prepared_linear   # lazy: pallas
        return int8_prepared_linear(x, w.q, w.scale, a_spec,
                                    out_dtype=x.dtype)
    xq = maybe_fake_quant(x, a_spec, key)
    wd = ((w.q.astype(jnp.float32) + w.zero) * w.scale).astype(x.dtype)
    return jnp.matmul(xq, wd)


def _dispatch(resolved: "Resolved", x: jnp.ndarray, w: jnp.ndarray,
              key) -> jnp.ndarray:
    recipe = resolved.recipe
    if isinstance(w, QState):
        return _prepared_matmul(resolved, x, w, key)
    if recipe is None or not recipe.any_linear_quant:
        return jnp.matmul(x, w)
    try:
        be = KERNEL_BACKENDS[resolved.backend]
    except KeyError:
        raise KeyError(f"unknown kernel backend {resolved.backend!r}; "
                       f"registered: {sorted(KERNEL_BACKENDS)}") from None
    if not be.supports(recipe):
        be = KERNEL_BACKENDS["fake_quant"]       # automatic fallback
    return be.fn(x, w, recipe, key)


# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PolicyRule:
    """One ordered pattern rule: ``block[lo:hi].role = recipe @ backend``.

    ``role`` is a name from :data:`ROLES` or ``"*"``; ``lo``/``hi`` bound the
    layer depth (python slice semantics, negatives relative to ``n_layers``,
    ``None`` = unbounded).  ``recipe=None`` means fp.  ``backend=None``
    inherits the policy's backend at resolution time (so rule order never
    changes which kernel runs).  Depth-bounded rules never match depth-less
    call sites (embed, lm-head, shared blocks).
    """
    role: str = "*"
    lo: Optional[int] = None
    hi: Optional[int] = None
    recipe: Optional[QuantRecipe] = None
    backend: Optional[str] = None

    @property
    def depth_bounded(self) -> bool:
        return self.lo is not None or self.hi is not None

    def matches(self, role: str, layer: Optional[int], n_layers: int = 0) -> bool:
        if self.role != "*" and self.role != role:
            return False
        if not self.depth_bounded:
            return True
        if layer is None:
            return False
        lo = self.lo if self.lo is not None else 0
        hi = self.hi if self.hi is not None else (n_layers or 1 << 30)
        if lo < 0:
            lo += n_layers
        if hi < 0:
            hi += n_layers
        return lo <= layer < hi

    def describe(self) -> str:
        pat = self.role
        if self.depth_bounded:
            lo = "" if self.lo is None else str(self.lo)
            hi = "" if self.hi is None else str(self.hi)
            pat = f"block[{lo}:{hi}].{pat}"
        spec = "fp" if self.recipe is None else \
            self.recipe.describe_compact().replace(",", "+")
        s = f"{pat}={spec}"
        if self.backend is not None:
            s += f"@{self.backend}"
        return s


@dataclasses.dataclass(frozen=True)
class Resolved:
    """Outcome of role resolution: what to run and on which backend."""
    recipe: Optional[QuantRecipe]
    backend: str = "fake_quant"


@dataclasses.dataclass(frozen=True)
class LinearCtx:
    """Call-site context for one quantized matmul.

    ``layer`` may be a python int (static), a traced scalar (inside the layer
    scan; requires ``n_layers``), or None for depth-less sites.
    """
    role: str
    layer: Union[int, jnp.ndarray, None] = None
    n_layers: int = 0
    rng: Optional[jax.Array] = None


# ---------------------------------------------------------------------------
# Policy
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class QuantPolicy:
    """Ordered pattern rules + default recipe + default backend.

    Resolution: first matching rule wins; otherwise ``(default, backend)``.
    Optimizer-moment specs (``adam_m1`` / ``adam_m2``) come from the default
    recipe -- moments are per-parameter, not per-role.
    """
    rules: Tuple[PolicyRule, ...] = ()
    default: Optional[QuantRecipe] = None
    backend: str = "fake_quant"

    # -- construction ------------------------------------------------------

    @classmethod
    def from_recipe(cls, recipe: Optional[QuantRecipe],
                    backend: str = "fake_quant") -> "QuantPolicy":
        """Legacy-equivalent policy: block linears get ``recipe``; the
        embedding table, lm-head, MoE router and the VLM patch adapter stay
        fp (exactly the seed ``quantized_linear(x, w, recipe)`` scoping).
        ``recipe.include_embeddings`` lifts the embed/lm-head exclusion."""
        rules = ()
        if not (recipe is not None and recipe.include_embeddings):
            rules += (PolicyRule(role="embed"), PolicyRule(role="lm_head"))
        rules += (PolicyRule(role="patch_proj"), PolicyRule(role="router"),
                  PolicyRule(role="kv_cache"))
        return cls(rules=rules, default=recipe, backend=backend)

    # -- optimizer-moment pass-through (duck-types a QuantRecipe) ----------

    @property
    def adam_m1(self) -> Optional[QuantSpec]:
        return self.default.adam_m1 if self.default is not None else None

    @property
    def adam_m2(self) -> Optional[QuantSpec]:
        return self.default.adam_m2 if self.default is not None else None

    # -- resolution --------------------------------------------------------

    def resolve(self, role: str, layer: Optional[int] = None,
                n_layers: int = 0) -> Resolved:
        for rule in self.rules:
            if rule.matches(role, layer, n_layers):
                return Resolved(rule.recipe, rule.backend or self.backend)
        return Resolved(self.default, self.backend)

    def depth_sensitive(self, role: str) -> bool:
        """Could resolution of ``role`` depend on the layer index?"""
        return any(r.depth_bounded for r in self.rules
                   if r.role in ("*", role))

    def effective_backend(self, role: str, layer: Optional[int] = None,
                          n_layers: int = 0) -> Tuple[str, Tuple[str, ...]]:
        """``(backend_name, caps)`` that :meth:`linear` will actually run for
        this role, with the registry fallback applied.  ``caps`` lists which
        passes execute real quantized kernels: ``('fwd', 'bwd')`` for the
        full int8 training path, ``('fwd',)`` for int8-forward-only, ``()``
        for the fake-quant reference einsum; backend name ``'fp'`` means a
        plain matmul (no quantization resolved)."""
        res = self.resolve(role, layer, n_layers)
        recipe = res.recipe
        if recipe is None or not recipe.any_linear_quant:
            return "fp", ()
        name, be = res.backend, KERNEL_BACKENDS[res.backend]
        if not be.supports(recipe):
            name, be = "fake_quant", KERNEL_BACKENDS["fake_quant"]
        if name == "fake_quant":
            return name, ()
        caps = ("fwd", "bwd") if be.bwd_supports(recipe) else ("fwd",)
        return name, caps

    def decode_attn_backend(self) -> Tuple[str, Tuple[str, ...]]:
        """``(backend_name, caps)`` for the KV-cache *consumption* path,
        :meth:`effective_backend`-style.  ``('fp', ())`` when the cache is
        stored fp; ``('<backend>', ('decode', 'prefill'))`` when a registered
        backend's attention kernels consume the stored payload directly
        (fused decode step + q8 prefill); ``('dequant', ())`` when the cache
        is quantized but no kernel fits the spec, i.e. the reference
        dequantize-on-read path runs.

        Unlike :meth:`linear` dispatch this is a capability scan, not a
        rule-backend lookup: ``fake_quant`` has no attention kernels, so a
        plain ``kv_cache=a8t`` rule (default backend) still finds the
        ``int8_pallas`` kernels.  The resolved rule backend is preferred when
        several backends qualify; ``REPRO_FUSED_DECODE=0`` opts out at the
        call site (see models/attention.py).

        ``decode_attn_supports`` is capability *metadata* (like
        ``bwd_supports``): the kernel entry points are not carried on the
        registry record, so ``int8_pallas`` is currently the only backend
        models/attention.py knows how to run -- a new backend registering
        this capability must also be threaded through ``_fused_kv_ok`` /
        the fused branches there.
        """
        spec = self.kv_spec()
        if spec is None:
            return "fp", ()
        preferred = self.resolve("kv_cache").backend
        names = [preferred] + [n for n in KERNEL_BACKENDS if n != preferred]
        for name in names:
            if KERNEL_BACKENDS[name].decode_attn_supports(spec):
                return name, ("decode", "prefill")
        return "dequant", ()

    def kv_spec(self) -> Optional[QuantSpec]:
        """Storage spec for the decode KV cache (role ``kv_cache``), or None
        for fp storage.  The spec is read from the resolved recipe's ``acts``
        component (falling back to ``weights`` -- cache entries are cached
        activations): ``kv_cache=a8t`` stores int8 K/V with one scale per
        (position, head) row.  Per-channel scales cannot key a (B,S,K,1)
        sidecar buffer and asymmetric/blockwise/stochastic codecs are not
        plumbed through the cache write, so those specs are rejected."""
        res = self.resolve("kv_cache")
        r = res.recipe
        if r is None:
            return None
        spec = r.acts if r.acts is not None else r.weights
        if spec is None:
            return None
        if (spec.granularity is Granularity.PER_CHANNEL
                or not spec.symmetric or spec.block_size
                or spec.sqrt_domain
                or spec.round_mode is not RoundMode.NEAREST):
            raise ValueError(
                f"kv_cache spec [{spec.describe()}] unsupported: the cache "
                "codec is symmetric nearest-rounded per-token (one scale per "
                "position x head) or per-tensor (per write-block)")
        return spec

    # -- dispatch ----------------------------------------------------------

    def linear(self, ctx: LinearCtx, x: jnp.ndarray, w: jnp.ndarray,
               b: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """The quantized matmul: resolve (role, layer) -> spec+backend, run.
        ``b`` is an optional bias added outside the quantized op (biases are
        not quantized -- paper scope is the matmul)."""
        with jax.named_scope("linear"):
            y = self._matmul(ctx, x, w)
        return y if b is None else y + b

    def _matmul(self, ctx, x, w):
        layer = ctx.layer
        static = layer is None or isinstance(layer, (int, _np.integer))
        if static or not self.depth_sensitive(ctx.role):
            res = self.resolve(ctx.role, layer if static else None,
                               ctx.n_layers)
            return _dispatch(res, x, w, ctx.rng)
        # traced layer index + depth-sensitive policy: group layers into
        # resolution classes and lax.switch between the (few) distinct ones.
        if not ctx.n_layers:
            raise ValueError(
                "depth-bounded policy rules need ctx.n_layers when the layer "
                "index is traced (inside the layer scan)")
        variants = [self.resolve(ctx.role, i, ctx.n_layers)
                    for i in range(ctx.n_layers)]
        uniq = []
        for v in variants:
            if v not in uniq:
                uniq.append(v)
        if len(uniq) == 1:
            return _dispatch(uniq[0], x, w, ctx.rng)
        class_of = jnp.asarray([uniq.index(v) for v in variants], jnp.int32)
        rng = ctx.rng
        branches = [
            (lambda x_, w_, res=res: _dispatch(res, x_, w_, rng))
            for res in uniq]
        return jax.lax.switch(class_of[layer], branches, x, w)

    def quantize_weight(self, role: str, w: jnp.ndarray) -> jnp.ndarray:
        """Weight-only qdq for non-matmul sites (embedding lookup, lm-head
        einsum).  STE: the table gradient flows unchanged.  No-op when the
        role resolves to fp (the default for embed/lm_head)."""
        res = self.resolve(role)
        spec = res.recipe.weights if res.recipe is not None else None
        if spec is None:
            return w
        return fake_quant(w, spec)

    def describe(self) -> str:
        parts = [r.describe() for r in self.rules]
        # only spell out the default when no depth-less wildcard rule covers it
        if not any(r.role == "*" and not r.depth_bounded for r in self.rules):
            spec = "fp" if self.default is None else \
                self.default.describe_compact().replace(",", "+")
            tail = f"*={spec}"
            if self.backend != "fake_quant":
                tail += f"@{self.backend}"
            parts.append(tail)
        return ",".join(parts)


#: The fp baseline policy: no rules, fp default -- every linear is a plain
#: matmul.  ``as_policy(None)`` returns this so model code never branches.
FP_POLICY = QuantPolicy()


def fallback_policy(policy: "QuantPolicy", mode: str = "fake_quant"
                    ) -> "QuantPolicy":
    """Stability-fallback variant of a policy -- the train sentinel's
    recovery action after a rollback (a temporary, step-indexed override:
    the trainer runs the fallback-compiled step for N steps, then re-engages
    the primary policy; see ``train/sentinel.py``).

    ``mode='fake_quant'`` keeps every resolved recipe (the quantization
    *error* stays, preserving the paper's methodology) but forces every rule
    and the policy default off the real-int8 kernels onto the ``fake_quant``
    reference einsum -- recovery from kernel-path numerical trouble without
    changing the optimization problem.

    ``mode='fp'`` additionally drops linear quantization (weights/acts/grads
    -> fp) from every rule and the default -- the Nielsen-et-al-style
    precision transition for when the int8 formulation itself destabilizes.

    Both modes PRESERVE the optimizer-moment specs (``adam_m1``/``adam_m2``)
    of the default recipe: the fallback step must consume and produce the
    exact same ``AdamState`` pytree (int8 ``QState`` payloads + sidecars) as
    the primary step, or rollback/re-engage could not hand states across.
    """
    if mode not in ("fake_quant", "fp"):
        raise ValueError(f"unknown fallback mode {mode!r} "
                         "(want 'fake_quant' or 'fp')")
    policy = as_policy(policy)

    def degrade(recipe: Optional[QuantRecipe]) -> Optional[QuantRecipe]:
        if recipe is None:
            return None
        if mode == "fake_quant":
            return recipe
        return dataclasses.replace(recipe, weights=None, acts=None,
                                   grads=None, grads_dx=None)

    rules = tuple(dataclasses.replace(r, recipe=degrade(r.recipe),
                                      backend="fake_quant")
                  for r in policy.rules)
    return QuantPolicy(rules=rules, default=degrade(policy.default),
                       backend="fake_quant")


def as_policy(obj: Union[None, QuantRecipe, QuantPolicy, str]) -> QuantPolicy:
    """Normalize the public ``recipe=`` / ``policy=`` surface: accepts None
    (fp), a QuantRecipe (wrapped via from_recipe), a QuantPolicy, or a policy
    string (parsed)."""
    if obj is None:
        return FP_POLICY
    if isinstance(obj, QuantPolicy):
        return obj
    if isinstance(obj, QuantRecipe):
        return QuantPolicy.from_recipe(obj)
    if isinstance(obj, str):
        return parse_policy(obj)
    raise TypeError(f"expected QuantRecipe / QuantPolicy / str / None, "
                    f"got {type(obj).__name__}")


# ---------------------------------------------------------------------------
# Policy string codec:  "embed=fp,block[0:2].*=fp,*=w8c+a8t@int8_pallas"
# ---------------------------------------------------------------------------

_PATTERN_RE = re.compile(
    r"^(?:(block\[)(-?\d+)?(:)?(-?\d+)?\]\.)?([a-z_][a-z0-9_]*|\*)$")


def _parse_pattern(pat: str) -> Tuple[str, Optional[int], Optional[int]]:
    m = _PATTERN_RE.match(pat.strip())
    if not m:
        raise ValueError(
            f"bad policy pattern {pat!r} (want 'role', '*', 'block[2].role' "
            "or 'block[0:4].*')")
    prefix, lo_s, colon, hi_s, role = m.groups()
    if role != "*" and role not in ROLES:
        raise ValueError(f"unknown role {role!r}; roles: {ROLES}")
    if prefix is None:
        return role, None, None
    if lo_s is None and hi_s is None:
        if colon is None:
            raise ValueError(f"bad policy pattern {pat!r}: block[] needs an "
                             "index or slice (block[2], block[0:4], block[:])")
        return role, 0, None            # block[:] -> every depth, but still
        #                                 depth-bounded: never matches the
        #                                 depth-less embed/lm_head/... sites
    lo = int(lo_s) if lo_s is not None else 0
    if colon is None:                       # block[i] -> exactly layer i
        if lo == -1:
            return role, -1, None           # block[-1] -> last layer
        return role, lo, lo + 1             # negative i: [-k, -k+1)
    hi = int(hi_s) if hi_s is not None else None
    return role, lo, hi


def _parse_value(spec: str) -> Tuple[Optional[QuantRecipe], Optional[str]]:
    """``spec[@backend]`` where spec is 'fp', a preset name, or a compact
    recipe string with '+' separators."""
    backend = None
    if "@" in spec:
        spec, backend = spec.split("@", 1)
        backend = backend.strip()
        if backend not in KERNEL_BACKENDS:
            raise ValueError(f"unknown kernel backend {backend!r}; "
                             f"registered: {sorted(KERNEL_BACKENDS)}")
    spec = spec.strip()
    recipe = None if spec == "fp" else get_recipe(spec)
    return recipe, backend


#: Roles the paper scopes out of block-linear quantization (plus the KV-cache
#: storage role, which is opt-in); parse_policy pins them fp unless a rule
#: names them explicitly (same as from_recipe).
_DEFAULT_FP_ROLES = ("embed", "lm_head", "patch_proj", "router", "kv_cache")


def parse_policy(text: str, backend: str = "fake_quant") -> QuantPolicy:
    """Parse a comma-separated rule list into a :class:`QuantPolicy`.

    Each entry is ``pattern=spec[@backend]``; earlier entries win.  A
    depth-less ``*`` entry also sets the policy default (and so the
    optimizer-moment specs).  Example::

        block[0:2].*=fp,*=w8c+a8t@int8_pallas

    The paper-scope exclusions (``embed``, ``lm_head``, ``router``,
    ``patch_proj`` stay fp) are seeded automatically so a wildcard means
    "every block linear", matching ``from_recipe``; name a role explicitly
    (``embed=w8c``) -- or put ``emb`` in the wildcard recipe -- to quantize
    it.
    """
    rules = []
    default: Optional[QuantRecipe] = None
    default_backend = backend
    for entry in text.split(","):
        entry = entry.strip()
        if not entry:
            continue
        if "=" not in entry:
            raise ValueError(f"bad policy entry {entry!r} (want pattern=spec)")
        pat, spec = entry.split("=", 1)
        role, lo, hi = _parse_pattern(pat)
        recipe, be = _parse_value(spec)
        rules.append(PolicyRule(role=role, lo=lo, hi=hi, recipe=recipe,
                                backend=be))
        if role == "*" and lo is None and hi is None and default is None:
            default = recipe
            if be is not None:
                default_backend = be
    for rule in rules:
        # optimizer moments are per-parameter, not per-role: they are only
        # honoured on the policy default (the depth-less '*' entry) -- reject
        # them elsewhere instead of silently running fp moments
        r = rule.recipe
        if (r is not None and (r.adam_m1 is not None or r.adam_m2 is not None)
                and r != default):
            raise ValueError(
                f"rule '{rule.describe()}' carries optimizer-moment specs "
                "(m1:/m2:), but moments are read from the depth-less '*' "
                "entry only -- move them there")
    named = {r.role for r in rules if r.role != "*"}
    include_emb = default is not None and default.include_embeddings
    exclusions = tuple(
        PolicyRule(role=role) for role in _DEFAULT_FP_ROLES
        if role not in named
        and not (include_emb and role in ("embed", "lm_head")))
    return QuantPolicy(rules=exclusions + tuple(rules), default=default,
                       backend=default_backend)
