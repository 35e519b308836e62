"""Deterministic, config-driven fault injection (``repro.config.fault_spec``).

Every failure domain with recovery code registers a NAMED SITE through
the one :func:`fault_point` helper -- the checkpoint writer/reader
(restore falls back to an older step), the gradient values of the train
step (the NaN guard skips the update), and the continuous serving
engine's per-request prefill / per-lane decode (a crashing lane
finalizes that request with ``status="failed"`` instead of killing the
batch).  A fault spec arms rules against those sites:

    config.update(fault_spec="ckpt.write:raise@step3;grad.values:nan@step5")

Grammar (``;``-separated rules)::

    <site-glob>:<action>[@step<N> | @<N>][~p<P>]

``site-glob``  fnmatch pattern over :data:`KNOWN_SITES` (must match >= 1)
``action``     ``raise`` -- raise :class:`InjectedFault` at the site;
               ``nan``   -- poison the value passing through the site
                            (floating leaves multiplied by NaN)
``@stepN``     fire only when the injection clock (:func:`set_step`, driven
               by the train loop) equals ``N``; omitted = every step
``~pP``        fire with probability ``P`` from a ``random.Random`` seeded
               by ``config.fault_seed`` at arm time -- deterministic per
               (spec, seed)

Zero overhead when disarmed: :func:`fault_point` is a single ``is None``
check, so the production hot path pays one attribute read per site.  The
injector records every firing (:func:`fired_events`) and every site it saw
while armed (:func:`seen_sites`) so CI can assert both the recovery
behaviour and the site coverage.

The config singleton re-arms the injector whenever ``fault_spec`` /
``fault_seed`` change (``config.update`` or the deprecated env mutation),
and this module syncs once at import, so either import order works.

Step-targeted rules and jit: ``grad.values`` sits inside the jitted train
step, which builds the NaN injection INTO the graph
(:func:`value_fault_steps` + :func:`nan_factor`), so it fires on the exact
step regardless of jit caching.  The checkpoint and serve sites run on
the host and fire when they are reached.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import random
import re

from repro.core.config import config
from repro.obs import events as obs_events

#: every registered fault site.  Adding a ``fault_point`` call to a new
#: failure domain means adding its name here -- the coverage test asserts
#: the two stay in sync by exercising each domain.
KNOWN_SITES = frozenset({
    "ckpt.write",                 # ckpt/checkpoint.py: manifest+leaf writer
    "ckpt.read",                  # ckpt/checkpoint.py: restore
    "grad.values",                # train loops: the gradient pytree itself
    "serve.prefill",              # serve/continuous.py: per-request prefill
    "serve.decode",               # serve/continuous.py: per-lane decode step
})

ACTIONS = ("raise", "nan")


class InjectedFault(RuntimeError):
    """The exception :func:`fault_point` raises for a ``raise`` rule."""

    def __init__(self, site: str, rule: "FaultRule"):
        super().__init__(f"injected fault at {site!r} "
                         f"(rule {rule.pattern}:{rule.action}, "
                         f"step {current_step()})")
        self.site = site
        self.rule = rule


@dataclasses.dataclass(frozen=True)
class FaultRule:
    pattern: str              # fnmatch glob over site names
    action: str               # "raise" | "nan"
    step: int | None = None   # None: every step
    prob: float = 1.0         # < 1.0: seeded coin flip per match


_RULE = re.compile(
    r"^(?P<pattern>[\w.*?\[\]-]+):(?P<action>\w+)"
    r"(?:@(?:step)?(?P<step>\d+))?"
    r"(?:~p(?P<prob>[0-9.]+))?$")


def parse_fault_spec(spec: str) -> tuple[FaultRule, ...]:
    """Parse a ``fault_spec`` string into rules; raises ValueError on bad
    grammar, unknown actions, or a pattern matching no known site."""
    rules = []
    for part in spec.split(";"):
        part = part.strip()
        if not part:
            continue
        m = _RULE.match(part)
        if m is None:
            raise ValueError(
                f"bad fault rule {part!r}; expected "
                "'<site-glob>:<action>[@stepN][~pP]'")
        action = m.group("action")
        if action not in ACTIONS:
            raise ValueError(
                f"unknown fault action {action!r} in {part!r}; "
                f"actions: {ACTIONS}")
        pattern = m.group("pattern")
        if not any(fnmatch.fnmatchcase(s, pattern) for s in KNOWN_SITES):
            raise ValueError(
                f"fault pattern {pattern!r} matches no known site; sites: "
                f"{sorted(KNOWN_SITES)}")
        prob = float(m.group("prob")) if m.group("prob") else 1.0
        if not 0.0 <= prob <= 1.0:
            raise ValueError(f"fault probability {prob} not in [0, 1]")
        rules.append(FaultRule(
            pattern=pattern, action=action,
            step=int(m.group("step")) if m.group("step") else None,
            prob=prob))
    return tuple(rules)


# -- armed state -------------------------------------------------------------

_ARMED: tuple[FaultRule, ...] | None = None
_RNG = random.Random(0)
_STEP = 0
_FIRED: list[dict] = []
_SEEN: set[str] = set()
_MAX_FIRED = 4096


def arm(spec: str, seed: int = 0) -> tuple[FaultRule, ...]:
    """Arm the injector with ``spec`` (validated); reseeds the probability
    stream so a (spec, seed) pair fires deterministically."""
    global _ARMED, _RNG
    rules = parse_fault_spec(spec)
    _ARMED = rules or None
    _RNG = random.Random(seed)
    return rules


def disarm() -> None:
    global _ARMED
    _ARMED = None


def armed_rules() -> tuple[FaultRule, ...]:
    return _ARMED or ()


def sync_from_config() -> None:
    """(Re-)arm from ``config.fault_spec`` / ``config.fault_seed``; called
    by the config singleton on updates and by this module at import."""
    spec = config.fault_spec
    if spec:
        arm(spec, seed=config.fault_seed)
    else:
        disarm()


def set_step(step: int) -> None:
    """Advance the injection clock; the train loop calls this once per
    step so ``@stepN`` rules target exact steps."""
    global _STEP
    _STEP = int(step)


def current_step() -> int:
    return _STEP


def fired_events() -> list[dict]:
    """Every fault fired since the last :func:`reset_events`."""
    return list(_FIRED)


def seen_sites() -> set[str]:
    """Sites that executed :func:`fault_point` while the injector was
    armed -- CI's coverage assert (arm a never-firing rule, exercise each
    failure domain, compare against :data:`KNOWN_SITES`)."""
    return set(_SEEN)


def reset_events() -> None:
    _FIRED.clear()
    _SEEN.clear()
    # Keep the bus's fault stream in lockstep with _FIRED (no-op when off).
    obs_events.drop("fault")


def _poison(value):
    """NaN-poison every floating leaf of ``value`` (non-float leaves and
    ``None`` pass through untouched)."""
    if value is None:
        return None
    import jax
    import jax.numpy as jnp

    def leaf(a):
        try:
            if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating):
                return a * jnp.float32(float("nan"))
        except TypeError:
            pass
        return a
    return jax.tree.map(leaf, value)


def fault_point(name: str, value=None):
    """THE fault site: every failure domain calls this with its site name.

    Disarmed (the default): returns ``value`` after one ``is None`` check.
    Armed: the site is recorded as seen, matching ``raise`` rules raise
    :class:`InjectedFault`, and matching ``nan`` rules return a
    NaN-poisoned copy of ``value``.
    """
    if _ARMED is None:
        return value
    if name not in KNOWN_SITES:
        raise ValueError(
            f"unregistered fault site {name!r}; add it to "
            f"repro.ft.inject.KNOWN_SITES (sites: {sorted(KNOWN_SITES)})")
    _SEEN.add(name)
    for rule in _ARMED:
        if not fnmatch.fnmatchcase(name, rule.pattern):
            continue
        if rule.step is not None and rule.step != _STEP:
            continue
        if rule.prob < 1.0 and _RNG.random() >= rule.prob:
            continue
        if len(_FIRED) < _MAX_FIRED:
            _FIRED.append({"site": name, "action": rule.action,
                           "step": _STEP, "pattern": rule.pattern})
            obs_events.emit("fault", name, action=rule.action, step=_STEP,
                            pattern=rule.pattern)
        if rule.action == "raise":
            raise InjectedFault(name, rule)
        value = _poison(value)
    return value


def value_fault_steps(name: str, action: str = "nan") \
        -> tuple[int | None, ...] | None:
    """The steps at which armed ``action`` rules target ``name`` -- or
    None when disarmed / nothing matches.  The jitted train step reads
    this at TRACE time and builds the injection into the graph
    (:func:`nan_factor`), because the step index is a traced value there
    and the Python-side clock cannot see it."""
    if _ARMED is None:
        return None
    _SEEN.add(name)
    steps = tuple(r.step for r in _ARMED
                  if r.action == action
                  and fnmatch.fnmatchcase(name, r.pattern))
    return steps or None


def nan_factor(step, steps: tuple[int | None, ...]):
    """An in-graph multiplier: NaN when the traced ``step`` matches any of
    ``steps`` (``None`` = every step), 1.0 otherwise."""
    import jax.numpy as jnp
    if any(s is None for s in steps):
        return jnp.float32(float("nan"))
    hit = jnp.zeros((), bool)
    for s in steps:
        hit = hit | (jnp.asarray(step, jnp.int32) == s)
    if _FIRED is not None and len(_FIRED) < _MAX_FIRED:
        _FIRED.append({"site": "grad.values", "action": "nan",
                       "step": tuple(int(s) for s in steps),
                       "pattern": "<in-graph>"})
        obs_events.emit("fault", "grad.values", action="nan",
                        step=[int(s) for s in steps], pattern="<in-graph>")
    return jnp.where(hit, jnp.float32(float("nan")), jnp.float32(1.0))


# Adopt any fault spec the config already carries (env var, or an update()
# that ran before this module was imported).
sync_from_config()
