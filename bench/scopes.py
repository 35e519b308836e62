"""Which conv pass, and whether glue, each device op of a step belongs to.

The program wraps every conv pass in a named scope ``conv_<pass>``
(``conv_forward``, ``conv_input_grad``, ``conv_weight_grad``, with ``_T``
for a transposed conv) and the layout rearrangements around each tap-GEMM
kernel call in ``glue``.  jax writes the scope path into each HLO
instruction's ``op_name`` metadata, wrapped by the transforms
(``jit(step)/transpose(jvp(conv_weight_grad))/glue/transpose``); a fusion
carries the path of its root, so glue that XLA fuses into a pass's compute
counts as the pass.

Two sources give the map ``{instruction name: op_name}``:

- ``from_hlo_text``: the compiled step's ``as_text()``;
- ``from_profile``: the ``tf_op`` stat that the profiler stores with each
  op of the ``XLA Ops`` line of a ``/device:TPU:<n>`` plane.  jax's
  ``ProfileData`` does not expose those stats, so the ``.xplane.pb`` is
  read here in the protobuf wire format (``XSpace.planes``,
  ``XPlane.event_metadata`` and ``stat_metadata``).

The metric readers take ``ctx["scopes"]`` where the harness gives one,
and otherwise the profile of the traced window.  A program without the
scopes maps no op to a pass, and the readers then report nothing.
"""

from __future__ import annotations

import functools
import glob
import os
import re

from bench.flops import PASSES
from bench.trace import DEVICE_PREFIX, op_name

GLUE = "glue"
#: a pass scope among the tokens of a path.
_PASS = re.compile(r"conv_(forward|input_grad|weight_grad)(?:_T)?")
_HLO_LINE = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?'
    r'op_name="([^"]*)"')


def classify(path: str) -> tuple[str | None, bool]:
    """``(pass, glue)`` of one ``op_name`` path: the first pass scope in
    it, transform wrappers and all, and whether ``glue`` follows it.  XLA
    joins the paths of merged ops with ``;``: the first with a pass
    counts."""
    for alt in path.split(";"):
        tokens = re.split(r"[/()]", alt)
        for i, token in enumerate(tokens):
            m = _PASS.fullmatch(token)
            if m:
                return m.group(1), GLUE in tokens[i + 1:]
    return None, False


def from_hlo_text(text: str) -> dict[str, str]:
    """``{instruction name: op_name}`` of every instruction with one."""
    out = {}
    for line in text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


# -- the .xplane.pb, read in the protobuf wire format -----------------------

def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint,
    the bytes (a memoryview) of a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _map_entry(buf) -> tuple:
    key = value = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _plane_ops(plane) -> dict[str, str]:
    """``{instruction name: tf_op path}`` of one device plane."""
    events, stat_names = [], {}
    for f, v in _fields(plane):
        if f == 4:                                 # event_metadata
            events.append(_map_entry(v)[1])
        elif f == 5:                               # stat_metadata
            key, meta = _map_entry(v)
            stat_names[key] = next((_text(m) for g, m in _fields(meta)
                                    if g == 2), "")
    out = {}
    for meta in events:
        name, path = None, None
        for f, v in _fields(meta):
            if f == 2:
                name = _text(v)
            elif f == 5:                           # one XStat
                stat = dict(_fields(v))
                if stat_names.get(stat.get(1)) != "tf_op":
                    continue
                if 7 in stat:                      # ref_value: a stat name
                    path = stat_names.get(stat[7], "")
                else:
                    path = _text(stat.get(5, stat.get(6, b"")))
        if name and path:
            # ``op_name:op_type``; jax leaves the type empty.
            out[op_name(name)] = path.rsplit(":", 1)[0]
    return out


def from_xspace(data: bytes) -> dict[str, str]:
    out = {}
    for f, plane in _fields(memoryview(data)):
        if f != 1:
            continue
        name = next((_text(v) for g, v in _fields(plane) if g == 2), "")
        if name.startswith(DEVICE_PREFIX) and \
                name[len(DEVICE_PREFIX):].isdigit():
            out.update(_plane_ops(plane))
    return out


@functools.lru_cache(maxsize=4)
def _from_file(path: str, mtime: float) -> dict[str, str]:
    with open(path, "rb") as f:
        return from_xspace(f.read())


def from_profile(profile_dir: str) -> dict[str, str]:
    """The map from the newest ``.xplane.pb`` under ``profile_dir``; empty
    where there is none."""
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return {}
    return _from_file(paths[-1], os.path.getmtime(paths[-1]))


def for_ctx(ctx: dict) -> dict[str, str]:
    if "scopes" in ctx:
        return ctx["scopes"]
    from bench.harness import PROFILE_DIR
    return from_profile(str(PROFILE_DIR))


def seconds(view, scopes: dict[str, str]) -> dict[str, float]:
    """Device seconds of the window's ops, averaged over the chips: per
    pass (``forward`` ... with its transposed twin), ``glue`` (a part of
    the passes' time), ``unscoped`` (in no pass) and ``mapped`` (with any
    ``op_name`` at all)."""
    out = dict.fromkeys((*PASSES, GLUE, "unscoped", "mapped"), 0.0)
    for _, name, a, b in view.ops:
        dt = (b - a) * 1e-9 / view.chips
        path = scopes.get(name)
        p, glue = classify(path) if path else (None, False)
        out[p or "unscoped"] += dt
        out[GLUE] += dt if glue else 0.0
        out["mapped"] += dt if path else 0.0
    return out


def pass_roofline(ctx: dict, pass_name: str) -> float | None:
    """Share of its least time that one pass reaches (%): the least time
    (``bench.flops.least_seconds``) of each needed conv's ``pass_name``,
    on whatever engine it ran, over the device time of the ops in that
    pass's scope.  None where the scope holds no device time."""
    from bench.flops import least_seconds
    spent = seconds(ctx["view"], for_ctx(ctx))[pass_name]
    least = sum(least_seconds(conv, ctx["peak"])
                for _, conv, p, needed, _ in ctx["passes"]
                if needed and p == pass_name)
    if spent == 0 or least == 0:
        return None
    return 100.0 * least * ctx["steps"] / spent
