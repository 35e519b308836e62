"""Mesh-parallel conv tests (repro.dist.conv_parallel).

Three layers of evidence, cheapest first:

  * host-side plan tests -- ``plan_conv_sharding`` only needs a ``.shape``
    mapping, so per-role degradation, halo math and the recorded reasons
    are pinned without any devices (hypothesis-swept over geometry);
  * a virtual-device matrix -- 8 CPU devices in a subprocess (the XLA flag
    must be set before jax initializes) run every shard role x
    {stride 1/2, dilation, transposed} cell under ``jax.value_and_grad``
    and compare forward/input-grad/weight-grad against the same conv on
    one device (same engines, so only the sharding differs) and against
    the single-device lax oracle (relative to its magnitude, since the
    engines round differently);
  * an HLO byte audit -- the compiled spatially-sharded forward's
    ``collective-permute`` traffic must equal the tap-derived halo bytes
    EXACTLY: nothing but the kept-tap overlap crosses the wire.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import conv as C
from repro.core.convspec import ConvSpec, ConvTransposeSpec
from repro.dist import conv_parallel as cp
from repro.kernels import ops

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


class StubMesh:
    """Plans only read axis sizes; no devices needed."""

    def __init__(self, **axes):
        self.shape = axes


# ---------------------------------------------------------------------------
# Halo math: shard_halo is the tap table's span, never the padded kernel's
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(taps_h=st.integers(min_value=1, max_value=3),
       taps_w=st.integers(min_value=1, max_value=3),
       dil=st.integers(min_value=1, max_value=3),
       s=st.integers(min_value=1, max_value=3),
       p=st.integers(min_value=0, max_value=4))
def test_shard_halo_matches_kept_tap_span(taps_h, taps_w, dil, s, p):
    """lo + hi == span - stride (the overlap of adjacent stride windows),
    lo == the low pad, and the span agrees with the planners' phase-split
    ``_taps_halo`` of the SAME kept-tap table."""
    k_h, k_w = (taps_h - 1) * dil + 1, (taps_w - 1) * dil + 1
    d = C.spec_dims((2, 3, 48, 48), (4, 3, taps_h, taps_w),
                    ConvSpec.make(stride=s, padding=p, dilation=dil))
    span_h, span_w = ops.tap_span(d)
    # every effective position is a kept tap only at multiples of dil, so
    # the span is the effective extent -- and nothing more
    assert (span_h, span_w) == (k_h, k_w)
    (lo_h, hi_h), (lo_w, hi_w) = ops.shard_halo(d)
    assert (lo_h, lo_w) == (p, p)
    assert lo_h + hi_h == span_h - s
    assert lo_w + hi_w == span_w - s
    taps = ops._forward_taps(ops._canonical(d))
    halo_h, halo_w = ops._taps_halo(taps)
    # phase-split rows and input-plane span measure the same footprint
    assert halo_h == (span_h - 1) // s
    assert halo_w == (span_w - 1) // s


def test_shard_halo_negative_hi_means_crop():
    """1x1 stride-2: adjacent windows skip rows entirely -- hi < 0."""
    d = C.spec_dims((1, 1, 8, 8), (1, 1, 1, 1), ConvSpec.make(stride=2))
    assert ops.shard_halo(d) == ((0, -1), (0, -1))


# ---------------------------------------------------------------------------
# plan_conv_sharding: per-role degradation with recorded reasons
# ---------------------------------------------------------------------------

def _plan(x_shape, w_shape, spec, par, mesh):
    return cp.plan_conv_sharding(x_shape, w_shape, spec, par, mesh)


def test_plan_full_assignment():
    mesh = StubMesh(data=2, model=2, sw=2)
    plan = _plan((4, 8, 16, 16), (6, 8, 3, 3),
                 ConvSpec.make(stride=2, padding=1),
                 cp.ConvParallel(batch=("data",), h="model", cout="sw"),
                 mesh)
    assert plan.roles == ("data", "h", "cout")
    assert plan.tag == "data+h+cout"
    assert plan.halo_h == (1, 0) and plan.dropped == ()


def test_plan_drop_reasons_are_specific():
    mesh = StubMesh(data=2, model=2)
    spec = ConvSpec.make(stride=2, padding=1)
    # indivisible batch drops ONLY the batch role
    plan = _plan((3, 8, 16, 16), (6, 8, 3, 3), spec,
                 cp.ConvParallel(batch=("data",), h="model"), mesh)
    assert plan.roles == ("h",)
    assert ("data", "batch 3 % 2 shards != 0") in plan.dropped
    # VALID-style padding: input != stride x output
    plan = _plan((4, 8, 16, 16), (6, 8, 3, 3), ConvSpec.make(stride=1),
                 cp.ConvParallel(h="model"), mesh)
    (role, why), = plan.dropped
    assert role == "h" and "non-uniform geometry" in why
    # halo wider than the shard block: single-hop exchange impossible
    plan = _plan((4, 8, 8, 8), (6, 8, 7, 7), ConvSpec.make(padding=3),
                 cp.ConvParallel(h="model"), StubMesh(model=4))
    (role, why), = plan.dropped
    assert role == "h" and "exceeds the 2-row shard block" in why
    # grouped conv refuses channel sharding (would split groups)
    plan = _plan((4, 8, 16, 16), (8, 4, 3, 3),
                 ConvSpec.make(padding=1, groups=2),
                 cp.ConvParallel(cin="data", cout="model"), mesh)
    assert plan.roles == ()
    assert all("grouped conv" in why for _, why in plan.dropped)
    # unknown mesh axis / axis claimed twice
    plan = _plan((4, 8, 16, 16), (6, 8, 3, 3), spec,
                 cp.ConvParallel(batch=("data",), cin="data", cout="sw"),
                 mesh)
    reasons = dict(plan.dropped)
    assert "already claimed" in reasons["cin"]
    assert "not in mesh" in reasons["cout"]


def test_plan_size_one_axes_drop_silently():
    plan = _plan((4, 8, 16, 16), (6, 8, 3, 3),
                 ConvSpec.make(stride=2, padding=1),
                 cp.ConvParallel(batch=("data",), h="model"),
                 StubMesh(data=1, model=1))
    assert plan.roles == () and plan.dropped == ()


def test_plan_transposed_channel_counts():
    """Transposed kernels are (C_in, C_out/g, kh, kw): the plan must read
    Cout from dim 1 (x groups), not dim 0."""
    mesh = StubMesh(data=2, model=3)
    plan = _plan((4, 8, 8, 8), (8, 6, 3, 3),
                 ConvTransposeSpec.make(stride=2, padding=1,
                                        output_padding=1),
                 cp.ConvParallel(cin="data", cout="model"), mesh)
    assert plan.transposed and plan.roles == ("cin", "cout")
    # and 6 % a 4-way axis correctly fails
    plan = _plan((4, 8, 8, 8), (8, 6, 3, 3),
                 ConvTransposeSpec.make(stride=2, padding=1,
                                        output_padding=1),
                 cp.ConvParallel(cout="model"), StubMesh(model=4))
    assert ("cout", "cout 6 % 4 shards != 0") in plan.dropped


@settings(max_examples=80, deadline=None)
@given(b=st.integers(min_value=1, max_value=6),
       c=st.integers(min_value=1, max_value=6),
       n=st.integers(min_value=1, max_value=6),
       h=st.integers(min_value=6, max_value=18),
       s=st.integers(min_value=1, max_value=2),
       nd=st.integers(min_value=1, max_value=4),
       nm=st.integers(min_value=1, max_value=4))
def test_plan_never_crashes_and_only_keeps_valid_roles(b, c, n, h, s, nd, nm):
    """Arbitrary (often indivisible) geometry: the plan always returns --
    surviving roles satisfy their invariants, dropped ones carry a reason."""
    mesh = StubMesh(data=nd, model=nm)
    spec = ConvSpec.make(stride=s, padding=1)
    x_shape, w_shape = (b, c, h, h), (n, c, 3, 3)
    try:
        d = C.spec_dims(x_shape, w_shape, spec)
    except Exception:
        return  # degenerate geometry the conv itself would reject
    if d.H_o < 1 or d.W_o < 1:
        return
    plan = _plan(x_shape, w_shape, spec,
                 cp.ConvParallel(batch=("data",), h="model", cin="model",
                                 cout="data"),
                 mesh)
    if plan.batch:
        assert b % nd == 0
    if plan.h:
        blk = h // nm
        assert h % nm == 0 and d.H_o % nm == 0 and h == s * d.H_o
        assert plan.halo_h[0] <= blk and plan.halo_h[1] <= blk
    if plan.cin:
        assert c % nm == 0
    if plan.cout:
        assert n % nd == 0
    # one axis never backs two roles
    claimed = [a for a in (plan.batch_spec, plan.h, plan.cin and "model",
                           plan.cout and "data") if a]
    axes = [a for a in (plan.h, plan.cin, plan.cout) if a] \
        + list(plan.batch)
    assert len(axes) == len(set(axes)), claimed
    for role, why in plan.dropped:
        assert role in cp.ROLES and isinstance(why, str) and why


# ---------------------------------------------------------------------------
# Policy resolution + hook lifecycle
# ---------------------------------------------------------------------------

def test_from_policy_resolution():
    mesh = StubMesh(data=4, model=2)
    tp = cp.ConvParallel.from_policy("tp", mesh)
    assert tp == cp.ConvParallel(batch=("data",), cout="model")
    dp = cp.ConvParallel.from_policy("dp_only", mesh)
    assert dp.batch == ("data", "model") and dp.cout is None
    sp = cp.ConvParallel.from_policy("spatial", mesh)
    assert sp.h == "model" and sp.batch == ("data",)
    rep = cp.ConvParallel.from_policy("tp_rep", mesh)
    assert rep == cp.ConvParallel(batch=("data",))
    pod = cp.ConvParallel.from_policy("tp", StubMesh(pod=2, data=4, model=2))
    assert pod.batch == ("pod", "data")
    with pytest.raises(ValueError, match="unknown conv mesh policy"):
        cp.ConvParallel.from_policy("bogus", mesh)


def test_conv_mesh_context_installs_and_clears_hook():
    assert C.MESH_LOWERING is None
    with cp.conv_mesh("tp"):
        assert C.MESH_LOWERING is cp._maybe_lower
        with cp.conv_mesh("spatial"):       # nesting keeps the hook
            assert C.MESH_LOWERING is cp._maybe_lower
        assert C.MESH_LOWERING is cp._maybe_lower
    assert C.MESH_LOWERING is None
    with cp.conv_mesh(None):                # None: explicit no-op
        assert C.MESH_LOWERING is None
    with pytest.raises(ValueError, match="unknown conv mesh policy"):
        cp.conv_mesh("bogus").__enter__()
    assert C.MESH_LOWERING is None


def test_no_mesh_falls_back_with_event():
    """Hook armed but no mesh anywhere: single-device result + event."""
    import jax
    import jax.numpy as jnp
    C.reset_dispatch_events()
    x = jnp.ones((1, 2, 8, 8), jnp.float32)
    w = jnp.ones((3, 2, 3, 3), jnp.float32)
    spec = ConvSpec.make(stride=2, padding=1)
    with cp.conv_mesh("tp"):
        y = C.conv2d(x, w, spec, "lax")
    assert y.shape == (1, 3, 4, 4)
    assert C.dispatch_events().get("mesh:no_mesh", 0) >= 1
    assert jax.numpy.allclose(y, C.conv2d(x, w, spec, "lax"))


# ---------------------------------------------------------------------------
# dist.sharding: conv kernels are spatial, not matmuls (regression pin)
# ---------------------------------------------------------------------------

def test_param_specs_conv_kernels_shard_cout_only():
    """The 4-D conv-kernel leaf rule: Cout over "model" (dim 0 regular,
    dim 1 transposed/"dec"), spatial dims NEVER sharded -- and the walk
    traverses the autoencoder's per-stage lists."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.dist import sharding as SH
    from repro.models import model as M

    mesh = StubMesh(data=1, model=1)   # size-1: _fit always accepts
    cfg = M.AutoencoderConfig(c_in=3, widths=(16, 32), k=3)
    params = jax.eval_shape(
        lambda: M.init_autoencoder(jax.random.PRNGKey(0), cfg))
    specs = SH.param_specs(params, mesh, "tp")
    assert isinstance(specs["enc"], list) and len(specs["enc"]) == 2
    for layer in specs["enc"]:
        assert layer["w"] == P("model", None, None, None)
    for layer in specs["dec"]:
        assert layer["w"] == P(None, "model", None, None)
    dp = SH.param_specs(params, mesh, "dp_only")
    for stage in ("enc", "dec"):
        for layer in dp[stage]:
            assert layer["w"] == P(None, None, None, None)
    # a 4-D kernel whose kh x kw happens to divide the mesh must still
    # never shard its spatial dims
    big = {"enc": [{"w": jax.ShapeDtypeStruct((8, 8, 4, 4), "float32")}]}
    spec = SH.param_specs(big, StubMesh(data=4, model=4), "tp")
    assert spec["enc"][0]["w"] == P("model", None, None, None)


# ---------------------------------------------------------------------------
# Virtual-device matrix: every role x {stride, dilation, transposed}
# ---------------------------------------------------------------------------

_MATRIX_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, os.path.join(%(root)r, "src"))
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import conv as C
    from repro.core.convspec import ConvSpec, ConvTransposeSpec
    from repro.dist import conv_parallel as cp

    assert len(jax.devices()) == 8, jax.devices()
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                ("data", "model", "sw"))
    results = []

    def check(tag, x, w, spec, par, want_event, transposed=False):
        C.reset_dispatch_events()
        conv = C.conv2d_transpose if transposed else C.conv2d

        def loss(x_, w_):
            with cp.conv_mesh(par, mesh):
                y = conv(x_, w_, spec, "auto")
            return jnp.sum(jnp.sin(y)), y

        def loss_ref(x_, w_):
            # Same engine policy, no mesh: the cell isolates the sharded
            # lowering (engine-vs-lax exactness is tested per engine).
            y = conv(x_, w_, spec, "auto")
            return jnp.sum(jnp.sin(y)), y

        def loss_lax(x_, w_):
            y = conv(x_, w_, spec, "lax")
            return jnp.sum(jnp.sin(y)), y

        (_, y_sh), g_sh = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(x, w)
        events = dict(C.dispatch_events())
        (_, y_rf), g_rf = jax.value_and_grad(
            loss_ref, argnums=(0, 1), has_aux=True)(x, w)
        (_, y_lx), g_lx = jax.value_and_grad(
            loss_lax, argnums=(0, 1), has_aux=True)(x, w)
        rel = lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        results.append({
            "tag": tag,
            "err_y": float(jnp.max(jnp.abs(y_sh - y_rf))),
            "err_dx": float(jnp.max(jnp.abs(g_sh[0] - g_rf[0]))),
            "err_dw": float(jnp.max(jnp.abs(g_sh[1] - g_rf[1]))),
            "rel_lax": max(rel(y_sh, y_lx), rel(g_sh[0], g_lx[0]),
                           rel(g_sh[1], g_lx[1])),
            "sharded_events": sorted(
                k for k in events if k.startswith("mesh:conv2d")),
            "want_event": want_event,
        })

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (4, 8, 16, 16), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (6, 8, 3, 3), jnp.float32)
    s2 = ConvSpec.make(stride=2, padding=1)
    s1 = ConvSpec.make(stride=1, padding=1)

    check("reg s2 data+h+cout", x, w, s2,
          cp.ConvParallel(batch=("data",), h="model", cout="sw"),
          "mesh:conv2d:data+h+cout")
    check("reg s1 data+h+w", x, w, s1,
          cp.ConvParallel(batch=("data",), h="model", w="sw"),
          "mesh:conv2d:data+h+w")
    check("reg s2 cin+cout", x, w, s2,
          cp.ConvParallel(cin="data", cout="model"),
          "mesh:conv2d:cin+cout")
    check("reg s2 w only", x, w, s2, cp.ConvParallel(w="sw"),
          "mesh:conv2d:w")
    check("reg dil2 data+h", x, w,
          ConvSpec.make(stride=1, padding=2, dilation=2),
          cp.ConvParallel(batch=("data",), h="model"),
          "mesh:conv2d:data+h")
    check("reg s1 policy tp", x, w, s1, "tp", "mesh:conv2d:data+cout")

    wt = jax.random.normal(jax.random.PRNGKey(2), (8, 6, 3, 3), jnp.float32)
    ts = ConvTransposeSpec.make(stride=2, padding=1, output_padding=1)
    check("tsp data+h+cin", x, wt, ts,
          cp.ConvParallel(batch=("data",), h="model", cin="sw"),
          "mesh:conv2d_T:data+h+cin", transposed=True)
    check("tsp h+w", x, wt, ts, cp.ConvParallel(h="model", w="sw"),
          "mesh:conv2d_T:h+w", transposed=True)
    check("tsp data+cout", x, wt, ts,
          cp.ConvParallel(batch=("data",), cout="model"),
          "mesh:conv2d_T:data+cout", transposed=True)

    # fallback execution: indivisible B and H run replicated with reasons
    C.reset_dispatch_events()
    x3 = jax.random.normal(key, (3, 8, 15, 16), jnp.float32)
    with cp.conv_mesh(cp.ConvParallel(batch=("data",), h="model"), mesh):
        y = C.conv2d(x3, w, ConvSpec.make(stride=1, padding=1), "lax")
    y_ref = C.conv2d(x3, w, ConvSpec.make(stride=1, padding=1), "lax")
    fb = {
        "events": {k: v for k, v in C.dispatch_events().items()
                   if k.startswith("mesh")},
        "reasons": [p["reason"] for p in C.policy_decisions()
                    if p["pass"] == "mesh"],
        "err": float(jnp.max(jnp.abs(y - y_ref))),
    }
    print(json.dumps({"cells": results, "fallback": fb}))
""")


@pytest.mark.dist
@pytest.mark.slow
def test_virtual_device_matrix_matches_single_device_oracle():
    out = subprocess.run(
        [sys.executable, "-c", _MATRIX_SCRIPT % {"root": ROOT}],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(res["cells"]) == 9
    for cell in res["cells"]:
        errs = (cell["err_y"], cell["err_dx"], cell["err_dw"])
        assert max(errs) < 1e-4, cell
        assert cell["rel_lax"] < 1e-5, cell
        assert cell["want_event"] in cell["sharded_events"], cell
    fb = res["fallback"]
    assert fb["err"] == 0.0
    assert "mesh:fallback" in fb["events"]
    assert fb["events"].get("mesh:drop:data") and fb["events"].get(
        "mesh:drop:h")
    assert any("batch 3 % 2" in r for r in fb["reasons"])
    assert any("15 % 2 shards" in r for r in fb["reasons"])


# ---------------------------------------------------------------------------
# HLO byte audit: the wire carries the tap halos and nothing else
# ---------------------------------------------------------------------------

_HALO_BYTES_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, os.path.join(%(root)r, "src"))
    import numpy as np, jax, jax.numpy as jnp
    assert len(jax.devices()) == 8        # lock the backend in BEFORE
    from jax.sharding import Mesh         # dryrun's 512-device default
    from repro.core import conv as C
    from repro.core.convspec import ConvSpec
    from repro.dist import conv_parallel as cp
    from repro.kernels import ops
    from repro.launch import dryrun

    mesh = Mesh(np.array(jax.devices()).reshape(8), ("model",))
    n = 8
    B, Cin, Cout, H, W = 2, 3, 5, 64, 64
    x = jnp.ones((B, Cin, H, W), jnp.float32)
    out = []
    for name, spec in (
            ("k3s1", ConvSpec.make(stride=1, padding=1)),
            ("k3s2", ConvSpec.make(stride=2, padding=1)),
            ("k5d2s1", ConvSpec.make(stride=1, padding=2, dilation=2))):
        k_taps = 3
        w = jnp.ones((Cout, Cin, k_taps, k_taps), jnp.float32)
        d = C.spec_dims(x.shape, w.shape, spec)
        (lo, hi), _ = ops.shard_halo(d)

        def fwd(x_, w_):
            with cp.conv_mesh(cp.ConvParallel(h="model"), mesh):
                return C.conv2d(x_, w_, spec, "lax")

        hlo = jax.jit(fwd).lower(x, w).compile().as_text()
        got = dryrun.collective_bytes(hlo, n)["collective-permute"]
        rows = max(lo, 0) + max(hi, 0)
        want = 4.0 * B * Cin * rows * W    # f32 halo slices, one hop each
        out.append({"case": name, "halo": [lo, hi],
                    "got": got, "want": want})
    print(json.dumps(out))
""")


@pytest.mark.dist
@pytest.mark.slow
def test_halo_exchange_bytes_equal_tap_derived_halos():
    """Exactly ``(lo + hi) * B * C * W * 4`` collective-permute bytes per
    spatially sharded forward: the exchanged halo IS ``shard_halo`` of the
    kept taps -- a stride-2 kernel exchanges ONE row, not two, and a
    dilated kernel's zero taps never cross the wire."""
    out = subprocess.run(
        [sys.executable, "-c", _HALO_BYTES_SCRIPT % {"root": ROOT}],
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    cases = json.loads(out.stdout.strip().splitlines()[-1])
    halos = {c["case"]: tuple(c["halo"]) for c in cases}
    assert halos == {"k3s1": (1, 1), "k3s2": (1, 0), "k5d2s1": (2, 2)}
    for c in cases:
        assert c["got"] == c["want"], c
