"""Jaxpr taint/dataflow pass: prove clip -> noise -> aggregate statically.

The DP guarantee of every clipping mode is a DATAFLOW property of the
train step: every path from a batch-derived value to a trainable
parameter's update sink must pass through a per-example clip-factor
multiply, and exactly one Gaussian draw — keyed by a leaf-unique PRNG
fold — must join each leaf's gradient before the optimizer consumes it.
This pass walks the closed jaxpr of `make_dp_train_step`'s step function
(plain or shard_map) and checks those properties per trainable leaf.

Taint lattice (monotone, finite -> the scan/while fixpoints terminate):

  raw      — value depends on the batch without an intervening clip factor
  clipped  — batch-derived but absorbed through a clip-factor multiply
  factor   — value produced under the `dp_clip_factor` named scope
  draws    — set of noise-draw ids (one per `random_bits` under a
             `dp_noise_add:<leaf>` scope) that reached this value
  key      — PRNG lineage: the set of fold-in constants applied to the
             base step key on the way to this value (None = not a key)

The clipping engine marks its semantics with `jax.named_scope`:
`dp_clip_factor` around factor computation (core.clipping / core.ghost)
and `dp_noise_add:<leaf-path>` around each leaf's draw (core.dp_sgd /
core.noise). Name stacks survive into (sub-)jaxprs, so the walk sees
them inside pjit bodies, scan bodies, shard_map regions and custom-vjp
transposes alike.

Soundness notes (why the green matrix is not a false negative):
  * the absorb rule fires only on multiplicative primitives
    (mul/div/dot_general/conv) with a factor/clipped operand — a raw
    value joined ADDITIVELY to anything stays raw;
  * scatter ops ignore their index operand's taint (embedding-gradient
    scatter-adds index by raw token ids; the indices choose WHERE a
    clipped update lands, they do not contribute magnitude);
  * unknown higher-order primitives fall back to joining every input
    into every output (conservative: can only create false POSITIVES).

The audit matrix pins `backend="xla"` (like launch.dryrun): the fused
Pallas `linear_clip` custom-call takes (a, g, c) with the factor applied
INSIDE the kernel, which an operand-level taint pass cannot see through.
The xla path is the bitwise-parity-tested reference for that kernel
(tests/test_kernels.py), so auditing it audits the same dataflow.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable

import jax
from jax.extend.core import Literal as _Literal

from repro.analysis.findings import ERROR, WARNING, Finding

CLIP_SCOPE = "dp_clip_factor"
NOISE_SCOPE = "dp_noise_add:"
_NOISE_LEAF = re.compile(r"dp_noise_add:([^/]+)")

# primitives where a clip-factor operand scales (rather than adds to) the
# result: a raw operand multiplied by a factor/clipped operand is clipped
_MULTIPLICATIVE = frozenset({
    "mul", "div", "dot_general", "conv_general_dilated",
})
# (operand, indices, updates): indices route, they do not contribute value
_SCATTER = frozenset({
    "scatter", "scatter-add", "scatter_add", "scatter_mul", "scatter_min",
    "scatter_max", "scatter_sub",
})
_DRAW_PRIMS = frozenset({"random_bits", "threefry2x32"})


@dataclasses.dataclass(frozen=True)
class Taint:
    raw: bool = False
    clipped: bool = False
    factor: bool = False
    draws: frozenset = frozenset()
    key: frozenset | None = None  # fold signature; None = not key-derived

    def join(self, other: "Taint") -> "Taint":
        if self == other:
            return self
        if self.key is None and other.key is None:
            key = None
        else:
            key = (self.key or frozenset()) | (other.key or frozenset())
        return Taint(self.raw or other.raw, self.clipped or other.clipped,
                     self.factor or other.factor, self.draws | other.draws,
                     key)


CLEAN = Taint()


@dataclasses.dataclass
class DrawSite:
    draw_id: str
    leaf: str | None          # dp_noise_add leaf name, None outside scopes
    key_sig: frozenset | None  # fold signature of the consumed key
    scope: str


class _State:
    def __init__(self):
        self.draws: dict[str, DrawSite] = {}  # keyed by structural id so
        #   scan-fixpoint re-evaluation never double-counts a draw


def _join_all(taints) -> Taint:
    out = CLEAN
    for t in taints:
        out = out.join(t)
    return out


def _unwrap(jx):
    """(jaxpr, consts?) from either a raw Jaxpr or a ClosedJaxpr.

    shard_map carries a RAW Jaxpr in params['jaxpr'] while pjit/scan carry
    ClosedJaxprs — both must recurse or the whole sharded path would be
    silently unanalyzed."""
    inner = getattr(jx, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner, True
    return jx, False


def _sub_jaxpr(eqn):
    for k in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = eqn.params.get(k)
        if sub is not None and (hasattr(sub, "eqns")
                                or hasattr(getattr(sub, "jaxpr", None),
                                           "eqns")):
            return sub
    return None


class _Interp:
    def __init__(self, state: _State):
        self.state = state

    def _read(self, env, atom) -> Taint:
        if isinstance(atom, _Literal):
            return CLEAN
        return env.get(atom, CLEAN)

    def eval_jaxpr(self, jx, in_taints, scope_prefix: str, id_prefix: str
                   ) -> list[Taint]:
        jaxpr, _ = _unwrap(jx)
        env: dict[Any, Taint] = {}
        for v in jaxpr.constvars:
            env[v] = CLEAN
        if len(in_taints) != len(jaxpr.invars):
            # operand-mapping mismatch (exotic call convention): smear the
            # join of everything over every binder — conservative
            smear = _join_all(in_taints)
            in_taints = [smear] * len(jaxpr.invars)
        for v, t in zip(jaxpr.invars, in_taints):
            env[v] = env.get(v, CLEAN).join(t) if v in env else t
        for idx, eqn in enumerate(jaxpr.eqns):
            outs = self._eval_eqn(eqn, [self._read(env, a)
                                        for a in eqn.invars],
                                  scope_prefix, f"{id_prefix}/{idx}")
            for v, t in zip(eqn.outvars, outs):
                env[v] = t
        return [self._read(env, a) for a in jaxpr.outvars]

    # -- one equation ------------------------------------------------------

    def _eval_eqn(self, eqn, ins: list[Taint], scope_prefix: str,
                  eqn_id: str) -> list[Taint]:
        name = eqn.primitive.name
        scope = scope_prefix + "/" + str(eqn.source_info.name_stack)
        nout = len(eqn.outvars)

        sub = self._higher_order(eqn, ins, scope, eqn_id)
        if sub is not None:
            outs = sub
        elif name in _SCATTER and len(ins) >= 3:
            outs = [_join_all([ins[0]] + ins[2:])] * nout
        elif name == "random_fold_in":
            outs = [self._fold(eqn, ins)] * nout
        elif name in _DRAW_PRIMS:
            outs = [self._draw(eqn, ins, scope, eqn_id)] * nout
        elif name in _MULTIPLICATIVE:
            joined = _join_all(ins)
            if joined.raw and any(t.factor or t.clipped for t in ins):
                joined = dataclasses.replace(joined, raw=False, clipped=True)
            outs = [joined] * nout
        else:
            outs = [_join_all(ins)] * nout

        if CLIP_SCOPE in scope:
            # everything produced under the marker IS factor data; norms
            # feeding it are consumed here, not leaked onward as raw
            outs = [dataclasses.replace(t, raw=False, factor=True)
                    for t in outs]
        return outs

    def _fold(self, eqn, ins: list[Taint]) -> Taint:
        joined = _join_all(ins)
        key = joined.key if joined.key is not None else frozenset()
        fold = eqn.invars[1] if len(eqn.invars) > 1 else None
        if isinstance(fold, _Literal):
            entry = f"lit:{fold.val}"
        else:
            # data-dependent fold (e.g. fold_in(key, dp_state.step)):
            # identified by the folded VALUE's identity, shared by every
            # consumer of the same fold
            entry = f"dyn:{id(fold)}"
        return dataclasses.replace(joined, key=key | {entry})

    def _draw(self, eqn, ins: list[Taint], scope: str, eqn_id: str) -> Taint:
        joined = _join_all(ins)
        m = _NOISE_LEAF.search(scope)
        leaf = m.group(1) if m else None
        key_sig = None
        for t in ins:
            if t.key is not None:
                key_sig = frozenset(t.key) if key_sig is None \
                    else key_sig | t.key
        if leaf is not None:
            self.state.draws[eqn_id] = DrawSite(eqn_id, leaf, key_sig, scope)
            return Taint(raw=joined.raw, clipped=joined.clipped,
                         factor=joined.factor, draws=joined.draws | {eqn_id})
        return dataclasses.replace(joined, key=None)

    # -- higher-order primitives -------------------------------------------

    def _higher_order(self, eqn, ins, scope, eqn_id):
        name = eqn.primitive.name
        p = eqn.params
        if name == "scan":
            return self._scan(eqn, ins, scope, eqn_id)
        if name == "while":
            return self._while(eqn, ins, scope, eqn_id)
        if name == "cond":
            branches = p.get("branches") or ()
            outs = None
            pred = ins[0] if ins else CLEAN
            for bi, br in enumerate(branches):
                got = self.eval_jaxpr(br, ins[1:], scope, f"{eqn_id}.b{bi}")
                outs = got if outs is None else [a.join(b) for a, b
                                                 in zip(outs, got)]
            if outs is None:
                return None
            return [t.join(dataclasses.replace(pred, key=None))
                    for t in outs]
        sub = _sub_jaxpr(eqn)
        if sub is None:
            return None
        return self.eval_jaxpr(sub, ins, scope, eqn_id)

    def _scan(self, eqn, ins, scope, eqn_id):
        p = eqn.params
        body = p["jaxpr"]
        nc, ncar = p.get("num_consts", 0), p.get("num_carry", 0)
        consts, carry, xs = ins[:nc], list(ins[nc:nc + ncar]), ins[nc + ncar:]
        outs = carry + [CLEAN] * (len(eqn.outvars) - ncar)
        for _ in range(64):
            outs = self.eval_jaxpr(body, consts + carry + xs, scope, eqn_id)
            new_carry = [a.join(b) for a, b in zip(carry, outs[:ncar])]
            if new_carry == carry:
                break
            carry = new_carry
        return carry + list(outs[ncar:])

    def _while(self, eqn, ins, scope, eqn_id):
        p = eqn.params
        body, cond = p.get("body_jaxpr"), p.get("cond_jaxpr")
        nb, ncnd = p.get("body_nconsts", 0), p.get("cond_nconsts", 0)
        if body is None:
            return None
        cconsts = ins[:ncnd]
        bconsts = ins[ncnd:ncnd + nb]
        carry = list(ins[ncnd + nb:])
        if cond is not None:
            self.eval_jaxpr(cond, cconsts + carry, scope, f"{eqn_id}.c")
        for _ in range(64):
            outs = self.eval_jaxpr(body, bconsts + carry, scope, eqn_id)
            new_carry = [a.join(b) for a, b in zip(carry, outs)]
            if new_carry == carry:
                break
            carry = new_carry
        return carry


# ---------------------------------------------------------------------------
# Driver: taint the step function's jaxpr, check the per-leaf invariants.
# ---------------------------------------------------------------------------


def _leaf_name(path) -> str:
    parts = []
    for entry in path:
        name = getattr(entry, "key", None)
        if name is None:
            name = getattr(entry, "idx", None)
        if name is None:
            name = getattr(entry, "name", str(entry))
        parts.append(str(name))
    return ".".join(parts)


def audit_train_step(
    step_fn: Callable,
    args: tuple,  # (params, opt_state, dp_state, batch, key) abstract/conc.
    *,
    private: bool = True,
    trainable_key: str | None = None,
) -> list[Finding]:
    """Taint-check one train step. Returns findings (empty = proven green).

    Rules:
      JAXPR-CLIP-PATH   — a trainable leaf's new value depends on the batch
                          WITHOUT passing a `dp_clip_factor` multiply
      JAXPR-NOISE-ONCE  — a trainable leaf receives != 1 noise draw
      JAXPR-KEY-LINEAGE — a noise draw's key is not folded from a static
                          leaf hash, or two leaves' keys share an identical
                          fold signature (the PR-6 `stable_hash` class and
                          the `noise._leaf_key` crc32-collision class)
    """
    closed = jax.make_jaxpr(step_fn)(*args)
    flat_in, _ = jax.tree_util.tree_flatten_with_path(tuple(args))
    in_taints = []
    for path, _leaf in flat_in:
        arg_idx = path[0].idx
        if arg_idx == 3:       # batch
            in_taints.append(Taint(raw=True))
        elif arg_idx == 4:     # PRNG key
            in_taints.append(Taint(key=frozenset()))
        else:                  # params / opt_state / dp_state
            in_taints.append(CLEAN)

    state = _State()
    interp = _Interp(state)
    out_taints = interp.eval_jaxpr(closed.jaxpr, in_taints, "", "")

    out_shapes = jax.eval_shape(step_fn, *args)
    flat_out, _ = jax.tree_util.tree_flatten_with_path(out_shapes)
    if len(flat_out) != len(out_taints):
        return [Finding("JAXPR-CLIP-PATH", WARNING,
                        f"output arity mismatch ({len(flat_out)} leaves vs "
                        f"{len(out_taints)} outvars); taint results not "
                        f"attributable", "outputs")]

    findings: list[Finding] = []
    if not private:
        return findings

    for (path, _leaf), taint in zip(flat_out, out_taints):
        if path[0].idx != 0:
            continue  # params output only; opt/dp/metrics are not the sink
        if trainable_key is not None and str(getattr(path[1], "key", "")) \
                != trainable_key:
            continue
        leaf = _leaf_name(path[1:])
        if taint.raw:
            findings.append(Finding(
                "JAXPR-CLIP-PATH", ERROR,
                "batch-derived gradient reaches the parameter update "
                "without passing a dp_clip_factor multiply", leaf))
        ndraws = len(taint.draws)
        if ndraws != 1:
            findings.append(Finding(
                "JAXPR-NOISE-ONCE", ERROR,
                f"{ndraws} noise draws reach this leaf's update "
                f"(exactly 1 required)", leaf))

    findings.extend(_key_lineage(state))
    return findings


def _key_lineage(state: _State) -> list[Finding]:
    findings = []
    by_sig: dict[frozenset, DrawSite] = {}
    for site in state.draws.values():
        if not site.key_sig:
            findings.append(Finding(
                "JAXPR-KEY-LINEAGE", ERROR,
                "noise draw consumes a key with no leaf-specific fold "
                "(base key reused verbatim)", site.leaf or site.scope))
            continue
        other = by_sig.get(site.key_sig)
        if other is not None and other.leaf != site.leaf:
            findings.append(Finding(
                "JAXPR-KEY-LINEAGE", ERROR,
                f"leaves {other.leaf!r} and {site.leaf!r} fold to an "
                f"IDENTICAL key signature — their noise draws are "
                f"correlated, breaking the Gaussian-mechanism sensitivity "
                f"bound", f"{other.leaf} ~ {site.leaf}"))
        else:
            by_sig[site.key_sig] = site
    # dedupe repeated pairs (stacked leaves can collide many times)
    seen, out = set(), []
    for f in findings:
        k = (f.rule, f.location)
        if k in seen:
            continue
        seen.add(k)
        out.append(f)
    return out
