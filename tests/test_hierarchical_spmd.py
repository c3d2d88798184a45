"""Hierarchical (pod, data) SlowMo under shard_map: equivalence + HLO pins.

Runs in a SUBPROCESS with 8 placeholder host-CPU devices (conftest must not
pollute the main process's device count).  Pins the acceptance criteria of
the hierarchical execution path on a (pods=2, data=2) mesh:

* TWO-LEVEL EQUIVALENCE ORACLE — a hierarchical mesh round must match a flat
  2-worker ``AxisBackend`` run whose per-worker batch is the concatenation of
  the pod's data-shard batches (within-pod AllReduce == one bigger-batch
  worker), to 1e-6 (relative to leaf scale: fp non-associativity of the
  two-level mean makes bitwise equality impossible, and e.g. ``slow_u`` is
  amplified by 1/gamma) over 3 rounds, across bases {local, ar, sgp},
  packed x tree layouts, and bf16 ``average_dtype``.  The bf16 BOUNDARY
  average is bit-identical (both backends round through the same bf16
  lattice); bf16 GOSSIP messages (PR 4: ppermutes honor average_dtype) are
  rounded every step, so a pre-existing ~1e-7 backend difference can flip a
  near-tie cast by one bf16 ulp (~3e-5 relative) — the sgp bf16 case
  asserts a 2-ulp bound instead;

* TWO-LEVEL HLO STRUCTURE — on the packed layout, per inner step exactly one
  gradient all-reduce whose replica groups span only the ``data`` axis, and
  per round boundary exactly one packed all-reduce whose groups span only
  ``pod``; gossip collective-permutes connect same-data-index devices across
  pods only.  Asserted through the shared contract auditor
  (``repro.analysis``): the census derived from the config must reconcile
  exactly against the lowered HLO's replica groups and permute pairs;

* SPEC UNIFICATION — the NamedSharding view (``sharding.batch_shardings``)
  and the shard_map path (``sharding.spmd_batch_specs``) produce the same
  batch PartitionSpecs.
"""
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.analysis import contract as contract_mod, hlo, rules
from repro.core import slowmo, packing
from repro.distributed import spmd, sharding
from repro.launch.mesh import make_hierarchical_layout, make_spmd_layout

assert len(jax.devices()) == 8
PODS, DP, B, D = 2, 2, 4, 16
W = PODS  # hierarchical workers = pods; each worker's batch B splits over DP

def loss_fn(params, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    return jnp.mean((pred - batch["y"]) ** 2)

def make_batches(seed, tau):
    k = jax.random.PRNGKey(seed)
    x = jax.random.normal(k, (tau, W, B, D))
    return {"x": x, "y": jnp.sum(x, -1) * 0.1}

layout = make_hierarchical_layout(PODS, DP)
assert layout.num_workers == PODS and layout.batch_shard == DP

# --- two-level equivalence oracle -----------------------------------------
# The SAME (tau, W, B, ...) batch arrays feed both runs: the flat oracle
# worker consumes its whole B, the hierarchical mesh shards B over 'data' —
# so each pod's data-shard batches concatenate to the oracle worker's batch.
CASES = [
    ("local_sgd+slowmo", False, None),
    ("local_sgd+slowmo", True, None),
    ("local_sgd+slowmo", True, "bf16"),
    ("ar_sgd", False, None),
    ("ar_sgd", True, None),
    ("sgp+slowmo", False, None),
    ("sgp+slowmo", True, None),
    ("sgp+slowmo", True, "bf16"),
]
for name, packed, avg in CASES:
    cfg = dataclasses.replace(
        slowmo.preset(name, num_workers=W, tau=3),
        packed=packed,
        average_dtype=jnp.bfloat16 if avg == "bf16" else None,
    )
    params0 = {"w": jax.random.normal(jax.random.PRNGKey(0), (D,)), "b": jnp.zeros(())}
    pack = slowmo.make_state_pack_spec(cfg, params0) if packed else None
    state_a = slowmo.init_slowmo(cfg, params0, pack=pack)
    state_m = jax.tree.map(jnp.array, state_a)  # real copy: fn_m donates its state
    fn_a = jax.jit(slowmo.make_slowmo_round(cfg, loss_fn, pack=pack))
    fn_m = spmd.make_spmd_slowmo_round(cfg, loss_fn, layout, pack=pack)
    for r in range(3):
        b = make_batches(r, cfg.tau)
        state_a, met_a = fn_a(state_a, b, 0.1)
        state_m, met_m = fn_m(state_m, b, 0.1)
    flat_a, _ = jax.tree_util.tree_flatten_with_path(state_a)
    flat_m = jax.tree.leaves(state_m)
    assert len(flat_a) == len(flat_m)
    # gossip bases with bf16 messages: every step's permuted message is
    # rounded to bf16, so a ~1e-7 backend difference entering a near-tie
    # cast flips one bf16 ulp (2^-15 relative ~ 3e-5); everything else
    # (incl. the bf16 boundary average alone) stays at 1e-6
    tol = 2 * 2.0**-15 if (avg == "bf16" and "sgp" in name) else 1e-6
    for (path, a), m in zip(flat_a, flat_m):
        a, m = np.asarray(a, np.float32), np.asarray(m, np.float32)
        scale = max(1.0, float(np.max(np.abs(m))) if m.size else 1.0)
        np.testing.assert_allclose(
            a / scale, m / scale, atol=tol, rtol=0,
            err_msg=f"{name} packed={packed} avg={avg}: {jax.tree_util.keystr(path)}")
    loss_tol = 1e-5 if tol == 1e-6 else 1e-3  # bf16 gossip: ulp flips reach the loss
    # scaled like the leaves: a ~1e2 loss is a few ulps of f32 at 1e-5
    loss_scale = max(1.0, abs(float(met_m["loss"])))
    assert abs(float(met_a["loss"]) - float(met_m["loss"])) / loss_scale < loss_tol, (name, packed, avg)
    print("HIER-EQ-OK", name, f"packed={int(packed)}", f"avg={avg or 'f32'}")

# --- two-level collective structure via the shared contract ----------------
# The Contract derived from (cfg, layout) IS the two-level pin: budgets carry
# exact (op, axes, bytes, dtype) multisets, and the rule engine reconciles
# the lowered HLO against them (replica-group axis match, counts, dtypes).
def audit_structure(name, tau):
    cfg = dataclasses.replace(
        slowmo.preset(name, num_workers=W, tau=tau), packed=True, unroll_inner=True)
    params0 = {"w": jax.random.normal(jax.random.PRNGKey(0), (D,)), "b": jnp.zeros(())}
    pack = slowmo.make_state_pack_spec(cfg, params0)
    state = slowmo.init_slowmo(cfg, params0, pack=pack)
    b = make_batches(0, tau)
    fn = spmd.make_spmd_slowmo_round(cfg, loss_fn, layout, pack=pack).build(state, b)
    txt = hlo.lowered_hlo_text(fn.lower(state, b, jnp.float32(0.1)))
    ct = contract_mod.round_contract(cfg, layout, pack=pack)
    hop_pairs = (contract_mod.gossip_hop_pairs(layout, cfg)
                 if cfg.base in ("sgp", "osgp", "dpsgd") else None)
    violations = rules.check_census(ct, layout.mesh, txt, hop_pairs=hop_pairs)
    assert not violations, (name, [v.as_dict() for v in violations[:5]])
    buf_bytes = pack.rows("float32") * packing.LANES * 4
    return ct, buf_bytes

TAU = 2
ct, buf_bytes = audit_structure("local_sgd+slowmo", TAU)
by_name = {}
for bgt in ct.budgets:
    by_name.setdefault(bgt.name, []).append(bgt)
# per inner step exactly ONE gradient all-reduce grouped over 'data' only,
# moving the whole packed gradient buffer (the census passing above proves
# the HLO matches; these assert the CONTRACT itself has the two-level shape)
(grad,) = by_name["pod-grad-sync"]
assert grad.axes == tuple(layout.batch_axes) and len(grad.sizes) == TAU, grad
assert all(s == buf_bytes for s in grad.sizes), (grad, buf_bytes)
# per round boundary exactly ONE packed all-reduce grouped over 'pod' only
(boundary,) = by_name["boundary-average"]
assert boundary.axes == tuple(layout.worker_axes), boundary
assert boundary.sizes == (buf_bytes,), (boundary, buf_bytes)
assert ct.boundary_bytes == buf_bytes
# everything else is the scalar loss pmean over ALL devices
(loss_b,) = by_name["loss-pmean"]
assert set(by_name) == {"pod-grad-sync", "boundary-average", "loss-pmean"}
assert loss_b.axes == tuple(layout.worker_axes) + tuple(layout.batch_axes)
assert all(s == 4 for s in loss_b.sizes), loss_b
print("HIER-HLO-OK all-reduce groups: "
      f"data x{len(grad.sizes)}, pod x{len(boundary.sizes)}, "
      f"scalar x{len(loss_b.sizes)}")

# gossip rolls stay pod-level: check_census above pins every collective-
# permute pair to the exponential-graph hop set, which for this layout is
# exactly the same-data-index cross-pod pairs — verify that identity here
ct_sgp, _ = audit_structure("sgp+slowmo", TAU)
hop_pairs = contract_mod.gossip_hop_pairs(
    layout, slowmo.preset("sgp+slowmo", num_workers=W, tau=TAU))
ids = np.vectorize(lambda d: d.id)(layout.mesh.devices)
pod_pairs = {(int(ids[p, d]), int(ids[(p + 1) % PODS, d]))
             for p in range(PODS) for d in range(DP)}
assert set(hop_pairs) == pod_pairs, (sorted(hop_pairs), sorted(pod_pairs))
assert any(b.op == "collective-permute" for b in ct_sgp.budgets)
print("HIER-CP-OK gossip permutes pinned to", len(pod_pairs), "pod-level pairs")

# --- one spec rule for both views (NamedSharding vs shard_map) -------------
for lay in (layout, make_spmd_layout(8)):
    shapes = {"x": jax.ShapeDtypeStruct((3, lay.num_workers, B, D), jnp.float32),
              "y": jax.ShapeDtypeStruct((3, lay.num_workers, B), jnp.float32)}
    gspmd = sharding.batch_shardings(lay, shapes)
    mapped = sharding.spmd_batch_specs(lay, shapes)
    for k in shapes:
        assert gspmd[k].spec == mapped[k], (k, gspmd[k].spec, mapped[k])
hier = sharding.spmd_batch_specs(layout, {"x": jnp.zeros((3, W, B, D))})
assert hier["x"] == P(None, "pod", "data"), hier
print("SPEC-UNIFY-OK")
print("ALL-OK")
"""


def test_hierarchical_matches_flat_oracle_and_hlo_pins():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True,
        text=True,
        timeout=900,
        # JAX_PLATFORMS=cpu: without it the stripped env lets the bundled
        # libtpu probe the GCP metadata server for ~8 min per subprocess
        env={
            "PYTHONPATH": os.path.join(REPO_ROOT, "src"),
            "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
            "JAX_PLATFORMS": "cpu",
        },
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "ALL-OK" in proc.stdout
    assert proc.stdout.count("HIER-EQ-OK") == 8
    assert "HIER-HLO-OK" in proc.stdout
    assert "HIER-CP-OK" in proc.stdout
    assert "SPEC-UNIFY-OK" in proc.stdout
