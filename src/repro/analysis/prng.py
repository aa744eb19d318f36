"""PRNG-discipline pass: statically prove every random draw has its own key.

BRIDGE's resilience analysis assumes independent randomness per edge, per
block, per tick (Chen/Su/Xu; the survey's replay/correlation failure class).
In JAX that invariant is a *syntactic* property of the jaxpr: a key reaching
two distinct ``random_bits`` computations without an intervening
``random_split`` / ``random_fold_in`` yields correlated draws.  This pass
walks the jaxpr of a traced-but-not-run program and flags exactly that.

The walk is a local value-numbering pass, not a simple def-use scan, because
the jaxpr obscures key identity three ways:

* the same raw ``uint32[2]`` key is re-``random_wrap``-ed at every use site
  (distinct Vars, one key) — structural value numbering unifies them, since
  identical primitives over identical inputs get identical numbers;
* ``random_split`` outputs are unwrapped and then sliced per subkey — slices
  with different ``start_indices`` hash to different numbers and correctly
  stay distinct keys;
* the high-level samplers appear as ``jit[name=_normal/...]`` sub-jaxprs —
  the walk recurses with the caller's value numbers bound to the callee's
  invars, so key identity crosses the call boundary.

Counting discipline (what is and is not a violation):

* a violation is one key value-number feeding **two or more distinct**
  ``random_bits`` value-numbers; two draws with *identical* numbers are
  identical values (value numbering's invariant) — that is the deliberate
  shared-randomness idiom (every node reading the same public coin, a
  loop-invariant draw equal to its hoisted form) and counts once.  The
  consumer's number includes the outermost sampler frame (the first
  ``jit[name=_normal/_uniform/...]`` wrapper on the path — ``normal``
  *internally* calls ``_uniform``, so the innermost frame cannot tell the
  two apart), so two *distributions* drawing the same raw bits from one
  key — bitwise equal bits but statistically correlated samples — stay
  distinct and are flagged;
* ``cond``/``switch`` regions merge per key by keeping the **largest single
  branch's** consumer set — only one branch executes, so the same key
  consumed once in each of nine attack-bank branches is one use, not nine
  (this under-approximates across-branch/after-branch mixes, never
  over-approximates: no false positives from exclusive control flow);
* ``scan``/``while`` carries and xs bind fresh numbers per body (the carried
  key evolves), while closed-over consts keep the caller's numbers — a
  body draw from an un-split const key unifies with any outer draw from it.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

#: sub-jaxpr-carrying params, recursed generically when not handled inline
_JAXPR_PARAMS = ("jaxpr", "call_jaxpr", "fun_jaxpr", "cond_jaxpr", "body_jaxpr")

#: key -> set-of-consumers tables; a region's analysis result
UseTable = dict[Any, frozenset]


@dataclasses.dataclass(frozen=True)
class KeyReuse:
    """One flagged key: feeds ``uses`` distinct random-bits computations."""

    key: str        # short rendering of the key's value number
    uses: int       # distinct-consumer count (>= 2)
    consumers: tuple[str, ...]  # distinct consumer renderings

    def __str__(self):
        return (f"key {self.key} consumed by {self.uses} distinct draws: "
                + "; ".join(self.consumers))


def _params_repr(params: dict) -> tuple:
    """Hashable, stable rendering of eqn params (sub-jaxprs by identity —
    they are interned per trace, and value numbers never cross traces)."""
    out = []
    for k in sorted(params):
        v = params[k]
        if k in _JAXPR_PARAMS or k == "branches":
            out.append((k, id(v)))
            continue
        try:
            hash(v)
            out.append((k, v))
        except TypeError:
            out.append((k, repr(v)))
    return tuple(out)


def _render(vn, depth: int = 0) -> str:
    if isinstance(vn, tuple):
        if depth >= 2:
            return "(..)"
        return "(" + ",".join(_render(x, depth + 1) for x in vn) + ")"
    return str(vn)


def _merge_seq(into: UseTable, region: UseTable) -> None:
    """Sequential composition: both regions execute — union consumer sets."""
    for key, cons in region.items():
        into[key] = into.get(key, frozenset()) | cons


def _merge_branches(regions: list[UseTable]) -> UseTable:
    """Exclusive composition: ONE region executes — per key, keep the
    largest single branch's consumer set (a sound lower bound on the worst
    path; unioning would fabricate cross-branch reuse)."""
    merged: UseTable = {}
    for region in regions:
        for key, cons in region.items():
            if len(cons) > len(merged.get(key, frozenset())):
                merged[key] = cons
    return merged


class _Walker:
    def __init__(self):
        self._n = 0
        self.uses: UseTable = {}
        self._frame: str | None = None  # outermost sampler (_-named jit) frame

    def fresh(self, label: str):
        self._n += 1
        return ("fresh", self._n, label)

    # -- value environment ---------------------------------------------------

    def _get(self, env: dict, atom) -> Any:
        if hasattr(atom, "val"):  # Literal
            v = np.asarray(atom.val)
            return ("lit", v.tobytes(), str(v.dtype), v.shape)
        if atom not in env:  # DropVar or untracked
            env[atom] = self.fresh("untracked")
        return env[atom]

    def _bind(self, inner_jaxpr, outer_ids: list, label: str) -> dict:
        env: dict = {}
        for i, iv in enumerate(inner_jaxpr.invars):
            env[iv] = outer_ids[i] if i < len(outer_ids) else self.fresh(label)
        for cv in inner_jaxpr.constvars:
            env[cv] = self.fresh(f"{label}:const")
        return env

    # -- the walk ------------------------------------------------------------

    def run(self, jaxpr, env: dict) -> UseTable:
        """Walk one (sub-)jaxpr; returns the region's private use table so
        callers can branch-merge it before folding in."""
        saved, self.uses = self.uses, {}
        for eqn in jaxpr.eqns:
            self._eqn(eqn, env)
        region, self.uses = self.uses, saved
        return region

    def _subregion(self, inner, outer_ids, label):
        env = self._bind(inner, outer_ids, label)
        return env, self.run(inner, env)

    def _eqn(self, eqn, env: dict):
        prim = eqn.primitive.name
        in_ids = [self._get(env, a) for a in eqn.invars]
        pr = _params_repr(eqn.params)

        if prim == "random_bits":
            consumer = ("random_bits", self._frame, tuple(in_ids), pr)
            key = in_ids[0]
            self.uses[key] = self.uses.get(key, frozenset()) | {consumer}
            # fall through to generic value numbering of the output

        elif prim == "jit" or "call_jaxpr" in eqn.params or "fun_jaxpr" in eqn.params:
            closed = (eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
                      or eqn.params.get("fun_jaxpr"))
            inner = closed.jaxpr if hasattr(closed, "jaxpr") else closed
            saved_frame = self._frame
            name = eqn.params.get("name")
            if saved_frame is None and isinstance(name, str) and name.startswith("_"):
                self._frame = name  # jax's samplers are _-named; first wins
            try:
                ienv, region = self._subregion(inner, in_ids, prim)
            finally:
                self._frame = saved_frame
            _merge_seq(self.uses, region)
            for ov, res in zip(eqn.outvars, inner.outvars, strict=True):
                env[ov] = self._get(ienv, res)
            return

        elif prim == "scan":
            inner = eqn.params["jaxpr"].jaxpr
            nc = eqn.params["num_consts"]
            ids = list(in_ids[:nc]) + [self.fresh("scan") for _ in inner.invars[nc:]]
            _, region = self._subregion(inner, ids, "scan")
            _merge_seq(self.uses, region)
            for ov in eqn.outvars:
                env[ov] = self.fresh("scan:out")
            return

        elif prim == "while":
            cn = eqn.params["cond_nconsts"]
            bn = eqn.params["body_nconsts"]
            for closed, consts in (
                (eqn.params["cond_jaxpr"], in_ids[:cn]),
                (eqn.params["body_jaxpr"], in_ids[cn:cn + bn]),
            ):
                inner = closed.jaxpr
                ids = list(consts) + [self.fresh("while")
                                      for _ in inner.invars[len(consts):]]
                _, region = self._subregion(inner, ids, "while")
                _merge_seq(self.uses, region)
            for ov in eqn.outvars:
                env[ov] = self.fresh("while:out")
            return

        elif prim == "cond":
            regions = []
            for br in eqn.params["branches"]:
                inner = br.jaxpr if hasattr(br, "jaxpr") else br
                _, region = self._subregion(inner, in_ids[1:], "branch")
                regions.append(region)
            _merge_seq(self.uses, _merge_branches(regions))
            for ov in eqn.outvars:
                env[ov] = self.fresh("cond:out")
            return

        else:
            # any other higher-order primitive (remat, custom_jvp, ...):
            # recurse into every sub-jaxpr param with the operand bindings
            recursed = False
            for k in _JAXPR_PARAMS:
                closed = eqn.params.get(k)
                if closed is None:
                    continue
                inner = closed.jaxpr if hasattr(closed, "jaxpr") else closed
                _, region = self._subregion(inner, in_ids, prim)
                _merge_seq(self.uses, region)
                recursed = True
            if recursed:
                for ov in eqn.outvars:
                    env[ov] = self.fresh(f"{prim}:out")
                return

        for i, ov in enumerate(eqn.outvars):
            env[ov] = (prim, tuple(in_ids), pr, i)


def find_reuse(closed_jaxpr) -> list[KeyReuse]:
    """All keys in ``closed_jaxpr`` feeding >= 2 distinct random-bits
    computations.  Empty list == the program is PRNG-clean."""
    w = _Walker()
    jaxpr = closed_jaxpr.jaxpr
    env = {v: ("arg", i) for i, v in enumerate(jaxpr.invars)}
    for i, cv in enumerate(jaxpr.constvars):
        env[cv] = ("const", i)
    region = w.run(jaxpr, env)

    out = []
    for key_vn, cons in sorted(region.items(), key=lambda kv: -len(kv[1])):
        if len(cons) < 2:
            continue
        out.append(KeyReuse(key=_render(key_vn), uses=len(cons),
                            consumers=tuple(sorted(_render(c) for c in cons))))
    return out


def check(fn, *args, **kwargs) -> list[KeyReuse]:
    """Trace ``fn(*args)`` (abstractly — nothing runs) and report reuse."""
    import jax

    return find_reuse(jax.make_jaxpr(fn, **kwargs)(*args))
