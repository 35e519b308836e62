"""The program's conv pass and glue scopes as ``bench/scopes.py`` reads
them: from a step compiled here on the CPU (the kernels interpreted), from
a synthetic ``.xplane.pb``, and on a trace recorded on the chip, with the
per-pass roofline, glue share and plan-time readers."""

import json
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, scopes, spec  # noqa: E402
from bench import trace as T  # noqa: E402
from bench.flops import PASSES  # noqa: E402

#: the kernels' names, which the tap_gemm_* readers match in op names.
KERNELS = ("tap_gemm", "tap_wgrad")
PKEYS = [f"{p}{t}" for p in PASSES for t in ("", "_T")]
DEVICE_READERS = ("forward_roofline", "input_grad_roofline",
                  "weight_grad_roofline", "glue_busy")


def _tokens(path: str) -> set[str]:
    import re
    return set(re.split(r"[/();]", path))


# -- a step compiled on the CPU ---------------------------------------------

@pytest.fixture(scope="module")
def step_scopes():
    """``{instruction: op_name}`` of one jitted step that pulls a gradient
    through a strided conv and a transposed conv, every pass on the
    tap-GEMM kernels."""
    import jax
    import jax.numpy as jnp
    from repro.core import conv
    from repro.core.convspec import ConvSpec, ConvTransposeSpec

    def loss(x, w, wt):
        y = conv.conv2d(x, w, ConvSpec.make(stride=2, padding=1), "pallas")
        z = conv.conv2d_transpose(
            y, wt, ConvTransposeSpec.make(stride=2, padding=1,
                                          output_padding=1), "pallas")
        return jnp.sum(z * z)

    args = (jnp.ones((2, 8, 16, 16)), jnp.ones((16, 8, 3, 3)),
            jnp.ones((16, 8, 3, 3)))
    conv.reset_dispatch_events()
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *args).compile()
    assert {k for k in conv.dispatch_events()} == {
        f"{pkey}:pallas" for pkey in PKEYS}
    return scopes.from_hlo_text(compiled.as_text())


def test_every_pass_has_its_scope(step_scopes):
    from repro.obs.trace import pass_scope
    found = set().union(*map(_tokens, step_scopes.values()))
    for pkey in PKEYS:
        assert pass_scope(pkey) in found, pkey


def test_the_kernels_have_glue_around_them(step_scopes):
    classes = [scopes.classify(p) for p in step_scopes.values()]
    glue_passes = {p for p, glue in classes if glue}
    assert glue_passes == set(PASSES)
    # The kernel itself lies in its pass, outside the glue.
    kernel = [scopes.classify(p) for p in step_scopes.values()
              if any(k in p for k in KERNELS)]
    assert kernel and all(p in PASSES and not glue for p, glue in kernel)


def test_no_scope_name_holds_a_kernel_name():
    from repro.obs.trace import GLUE_SCOPE, pass_scope
    for name in [GLUE_SCOPE, *map(pass_scope, PKEYS)]:
        assert not any(k in name for k in KERNELS), name
    assert GLUE_SCOPE == scopes.GLUE


@pytest.mark.parametrize("path, want", [
    ("jit(step)/transpose(jvp(conv_weight_grad))/glue/transpose",
     ("weight_grad", True)),
    ("jit(step)/jvp(conv_forward_T)/tap_gemm_phased/pallas_call",
     ("forward", False)),
    ("jit(step)/glue/jvp(conv_forward)/pad", ("forward", False)),
    ("jit(step)/x/reshape;jit(step)/jvp(conv_input_grad)/glue/transpose",
     ("input_grad", True)),
    ("jit(step)/jvp()/reduce_sum", (None, False)),
    ("jit(step)/conv_forwards/add", (None, False)),
])
def test_classify(path, want):
    assert scopes.classify(path) == want


# -- a synthetic .xplane.pb --------------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key: int, value: bytes) -> bytes:
    return _field(1, key) + _field(2, value)


def _plane(name: str, stat_names: dict, events: dict) -> bytes:
    """An XPlane: ``events`` maps an id to ``(name, [(stat id, field,
    value)])``."""
    out = _field(2, name) + _field(3, _field(2, "XLA Ops"))
    for i, (ev_name, stats) in events.items():
        meta = _field(1, i) + _field(2, ev_name) + b"".join(
            _field(5, _field(1, s) + _field(f, v)) for s, f, v in stats)
        out += _field(4, _entry(i, meta))
    for i, stat in stat_names.items():
        out += _field(5, _entry(i, _field(1, i) + _field(2, stat)))
    return out


def test_xplane_tf_op_stats_give_the_map(tmp_path):
    path = "jit(step)/jvp(conv_forward)/tap_gemm/pallas_call"
    stats = {1: "tf_op", 2: "flops", 3: path + ":"}
    device = _plane("/device:TPU:0", stats, {
        10: ("%fusion.3 = f32[8]{0} fusion(), kind=kLoop",
             [(2, 3, 64), (1, 5, "jit(step)/jvp(conv_forward)/glue/pad:")]),
        11: ("%tap_gemm.2 = f32[8]{0} custom-call()", [(1, 7, 3)]),
        12: ("%copy-done = f32[8]{0} copy-done()", [(2, 3, 8)]),
    })
    host = _plane("/host:CPU", {1: "tf_op"},
                  {10: ("%other.1 = f32[]", [(1, 5, "host/only:")])})
    data = _field(1, device) + _field(1, host)
    want = {"fusion.3": "jit(step)/jvp(conv_forward)/glue/pad",
            "tap_gemm.2": path}
    assert scopes.from_xspace(data) == want
    prof = tmp_path / "plugins" / "profile" / "1"
    prof.mkdir(parents=True)
    (prof / "host.xplane.pb").write_bytes(data)
    assert scopes.from_profile(str(tmp_path)) == want
    assert scopes.from_profile(str(tmp_path / "none")) == {}


# -- readers ------------------------------------------------------------------

def test_readers_report_nothing_without_scopes():
    """A program without the scopes maps no op to a pass: the readers
    give no number, and no zero."""
    events = {"device": [[0, "tap_gemm.1", 0, 10 ** 6]],
              "host": [["bench:window", 0, 2 * 10 ** 6]]}
    from bench.flops import Conv
    c = Conv(2, 3, 16, 8, 3, 2, 1)
    ctx = {"view": T.TraceView(events, chips=1), "steps": 1,
           "passes": [(None, c, p, True, "pallas") for p in PASSES],
           "peak": spec.peaks("TPU v5 lite"), "scopes": {}}
    for name in DEVICE_READERS:
        assert spec.metric_reader(name)(ctx) is None, name


def test_plan_s_reads_the_program_count(monkeypatch):
    from repro.kernels import ops
    assert spec.metric_reader("plan_s")({}) == ops.plan_seconds()
    monkeypatch.setitem(sys.modules, "repro.kernels.ops",
                        types.SimpleNamespace())
    assert spec.metric_reader("plan_s")({}) is None


# -- a trace recorded on the chip --------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    """Five autoencoder steps traced on a TPU v5 lite, with the scope map
    and each pass's engine, as the readers' context."""
    rec = json.loads((ROOT / "tests/bench/data/autoencoder_scopes.json")
                     .read_text())
    kind = harness.build(spec.load_cell("autoencoder_256.b8"))
    decisions = [{"pass": p, "transpose": t, "dims": dims, "engine": e}
                 for (p, t, dims), e in rec["engines"]]
    return {"view": T.TraceView(rec, chips=1), "steps": rec["steps"],
            "passes": harness._engines(kind, decisions),
            "peak": spec.peaks("TPU v5 lite"), "scopes": rec["scopes"]}


@pytest.mark.parametrize("name", DEVICE_READERS)
def test_recorded_readers_are_shares(recorded, name):
    value = spec.metric_reader(name)(recorded)
    assert value is not None and 0 < value <= 100, (name, value)


def test_recorded_passes_and_the_rest_make_up_busy_time(recorded):
    view, sec = recorded["view"], scopes.seconds(recorded["view"],
                                                 recorded["scopes"])
    total = sum(sec[p] for p in PASSES) + sec["unscoped"]
    assert total == pytest.approx(view.busy_s, rel=1e-9)
    assert 0 < sec[scopes.GLUE] < sum(sec[p] for p in PASSES)
    # Ops with no op_name at all (async copies XLA adds) are a sliver.
    assert sec["mapped"] > 0.97 * view.busy_s


def test_recorded_kernels_lie_in_their_passes(recorded):
    """Every kernel launch of a step is in a pass scope and outside the
    glue, and the kernel readers still find all 11 by name."""
    kernels = {name for _, name, _, _ in recorded["view"].ops
               if any(k in name for k in KERNELS)}
    assert len(kernels) == 11
    for name in kernels:
        p, glue = scopes.classify(recorded["scopes"][name])
        assert p in PASSES and not glue, name
