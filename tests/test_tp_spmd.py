"""Tensor-parallel workers: the full (pod, data, model) mesh through the
shard_map SlowMo round.

Runs in a SUBPROCESS with 8 placeholder host-CPU devices.  Pins the
acceptance criteria of the TP refactor on a (pods=2, data=2, model=2) mesh:

* THREE-LEVEL EQUIVALENCE — a TP round (params model-sharded per
  ``sharding.model_spec_tail``, loss running column-parallel-in /
  row-parallel-out matmuls with psum over ``model`` via the backend's
  model-axis hooks) must match the SAME ``models.tp.TPLoss`` run on the
  (pods=2, data=2) TP-free mesh — where every hook is the identity — to
  1e-6 (leaf-scaled) over 3 rounds, across {local, ar, sgp} x packed/tree
  x bf16 ``average_dtype`` (bf16 gossip messages: 2-ulp bound, see
  test_hierarchical_spmd).  At most 2 elements of a leaf may leave that
  bound, each only as far as its cause allows: on a bf16 wire one bf16 ulp
  of its value (a near-tie cast flipped); on an f32 wire only in
  ``slow_u``, whose ``gamma * slow_u`` may be 4 f32 ulps of the outer
  parameters off (an average a few ulps off, times 1/gamma);

* THREE-LEVEL HLO STRUCTURE — per inner step exactly the loss's model-axis
  psums grouped over ``model`` only plus ONE packed gradient all-reduce
  grouped over ``data`` only; per round boundary exactly ONE packed
  all-reduce grouped over ``pod`` only whose buffer is the LOCAL model
  shard — half the bytes of the TP-free packing (traffic ∝ 1/TP); gossip
  collective-permutes connect same-(data, model)-index devices across pods;

* ONE RULE, BOTH VIEWS — the field-by-field spec rule
  (``slowmo_state_specs``) and the mesh rule (``spmd_state_specs``) agree
  leaf-for-leaf on a TP state, and batch specs replicate over ``model`` in
  both views.
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
from repro.launch.mesh import make_hierarchical_layout
from repro.models import tp as tp_lib

assert len(jax.devices()) == 8
PODS, DP, TP, B = 2, 2, 2, 4
LR = 0.1  # gamma of every round below
W = PODS

tp_layout = make_hierarchical_layout(PODS, DP, TP)
oracle_layout = make_hierarchical_layout(PODS, DP)
assert tp_layout.model_shard == TP and tp_layout.num_workers == W

# Megatron-style two-matmul loss: w_in column-parallel (sharded on its
# output dim), w_down row-parallel (sharded on its contracting dim, psum),
# b0/b replicated — b0 sits UPSTREAM of the column matmul, so its gradient
# is only complete through copy_to_tp's psum backward (the f operator).
def make_loss():
    def factory(backend):
        def loss_fn(params, batch):
            h = tp_lib.copy_to_tp(backend, batch["x"] + params["b0"])
            h = jnp.tanh(h @ params["w_in"])
            pred = tp_lib.reduce_from_tp(backend, h @ params["w_down"]) + params["b"]
            return jnp.mean((pred - batch["y"]) ** 2)
        return loss_fn
    return tp_lib.TPLoss(factory)

loss = make_loss()

def make_batches(seed, tau, D, O):
    k = jax.random.PRNGKey(seed)
    x = jax.random.normal(k, (tau, W, B, D))
    return {"x": x, "y": (jnp.sum(x, -1, keepdims=True) * 0.1) @ jnp.ones((1, O))}

def make_params(D, H, O):
    return {
        "w_in": 0.3 * jax.random.normal(jax.random.PRNGKey(0), (D, H)),
        "w_down": 0.3 * jax.random.normal(jax.random.PRNGKey(1), (H, O)),
        "b0": jnp.zeros((D,)),
        "b": jnp.zeros((O,)),
    }

D, H, O = 16, 32, 8
params0 = make_params(D, H, O)
dims = sharding.model_shard_dims(params0, TP)
assert dims["w_in"] == 1 and dims["w_down"] == 0  # column in, row out
assert dims["b0"] is None and dims["b"] is None

# --- three-level equivalence: TP mesh vs TP-free (pod, data) mesh ----------
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
    pack_tp = slowmo.make_state_pack_spec(cfg, params0, layout=tp_layout) if packed else None
    pack_or = slowmo.make_state_pack_spec(cfg, params0) if packed else None
    # fresh param copies per state: the mesh rounds DONATE their state
    st_tp = slowmo.init_slowmo(cfg, jax.tree.map(jnp.array, params0), pack=pack_tp)
    st_or = slowmo.init_slowmo(cfg, jax.tree.map(jnp.array, params0), pack=pack_or)
    fn_tp = spmd.make_spmd_slowmo_round(cfg, loss, tp_layout, pack=pack_tp)
    fn_or = spmd.make_spmd_slowmo_round(cfg, loss, oracle_layout, pack=pack_or)
    for r in range(3):
        b = make_batches(r, cfg.tau, D, O)
        st_tp, met_tp = jax.block_until_ready(fn_tp(st_tp, b, LR))
        st_or, met_or = jax.block_until_ready(fn_or(st_or, b, LR))
    if packed:
        st_tp = packing.unpack_state(pack_tp, st_tp)
        st_or = packing.unpack_state(pack_or, st_or)
    flat_tp, _ = jax.tree_util.tree_flatten_with_path(st_tp)
    flat_or = jax.tree.leaves(st_or)
    assert len(flat_tp) == len(flat_or)
    outer_or = {jax.tree_util.keystr(p): np.asarray(v, np.float32)
                for p, v in jax.tree_util.tree_flatten_with_path(st_or.outer_params)[0]}
    # bf16 gossip messages are rounded every step: a tiny cross-compilation
    # difference entering a near-tie cast flips one bf16 ulp (2^-15)
    tol = 2 * 2.0**-15 if (avg == "bf16" and "sgp" in name) else 1e-6
    for (path, a), m in zip(flat_tp, flat_or):
        key = jax.tree_util.keystr(path)
        a, m = np.asarray(a, np.float32), np.asarray(m, np.float32)
        diff = np.abs(a - m)
        scale = max(1.0, float(np.max(np.abs(m))) if m.size else 1.0)
        flips = diff / scale > tol
        if not flips.any():
            continue
        # at most 2 elements of a leaf may leave the bound (seen: one element,
        # held by both worker copies), each only as far as its cause allows
        assert flips.sum() <= 2, (name, packed, avg, key, int(flips.sum()))
        units, ref = 1.0, m
        if key.startswith(".slow_u"):
            # slow_u = beta u + (x0 - avg) / gamma: the average's error arrives
            # x 1/gamma, so gamma * slow_u is held against the outer parameter
            units, ref = LR, outer_or[key[len(".slow_u"):]]
        if avg == "bf16":
            # the row-parallel psum reorders the f32 contraction, so the inner
            # endpoints differ by f32 ulps; where one sits on a bf16 rounding
            # tie the wire cast flips it by at most one bf16 ulp of its value
            bound = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(ref), 2.0**-126))) - 7)
        else:
            # f32 wire: only slow_u, from an average 4 f32 ulps off at most
            assert key.startswith(".slow_u"), (name, packed, avg, key)
            bound = np.full(ref.shape, 2.0**-21 * max(1.0, float(np.max(np.abs(ref)))))
        assert np.all(units * diff[flips] <= bound[flips]), (
            name, packed, avg, key, float(np.max(units * diff[flips] / bound[flips])))
    loss_tol = 1e-5 if tol == 1e-6 else 1e-3
    # scaled like the leaves: a ~1e2 loss is a few ulps of f32 at 1e-5
    loss_scale = max(1.0, abs(float(met_or["loss"])))
    assert abs(float(met_tp["loss"]) - float(met_or["loss"])) / loss_scale < loss_tol, (name, packed, avg)
    print("TP-EQ-OK", name, f"packed={int(packed)}", f"avg={avg or 'f32'}")

# --- three-level collective structure (packed, exact 1/TP bytes) -----------
# leaf sizes chosen so shard rows are exactly half the TP-free rows (no
# alignment slack): 128*512 + 512*128 = 128 rows full, 64 per shard
DH, HH = 128, 512
hlo_params = {
    "w_in": 0.02 * jax.random.normal(jax.random.PRNGKey(2), (DH, HH)),
    "w_down": 0.02 * jax.random.normal(jax.random.PRNGKey(3), (HH, DH)),
}

def hlo_loss_factory(backend):
    def loss_fn(params, batch):
        h = jnp.tanh(tp_lib.copy_to_tp(backend, batch["x"]) @ params["w_in"])
        pred = tp_lib.reduce_from_tp(backend, h @ params["w_down"])
        return jnp.mean((pred - batch["y"]) ** 2)
    return loss_fn
hlo_loss = tp_lib.TPLoss(hlo_loss_factory)

MESH = tp_layout.mesh

def audit_structure(name, tau, max_model_bytes=None):
    cfg = dataclasses.replace(
        slowmo.preset(name, num_workers=W, tau=tau), packed=True, unroll_inner=True)
    pk = slowmo.make_state_pack_spec(cfg, hlo_params, layout=tp_layout)
    state = slowmo.init_slowmo(cfg, jax.tree.map(jnp.array, hlo_params), pack=pk)
    b = make_batches(0, tau, DH, DH)
    fn = spmd.make_spmd_slowmo_round(cfg, hlo_loss, tp_layout, pack=pk).build(state, b)
    txt = hlo.lowered_hlo_text(fn.lower(state, b, jnp.float32(0.1)))
    ct = contract_mod.round_contract(
        cfg, tp_layout, pack=pk, model_collective_max_bytes=max_model_bytes)
    hop_pairs = (contract_mod.gossip_hop_pairs(tp_layout, cfg)
                 if cfg.base in ("sgp", "osgp", "dpsgd") else None)
    violations = rules.check_census(ct, MESH, txt, hop_pairs=hop_pairs)
    assert not violations, (name, [v.as_dict() for v in violations[:5]])
    return ct, pk, txt

TAU = 2
ct, pk, txt = audit_structure("local_sgd+slowmo", TAU)
shard_bytes = pk.shard.rows("float32") * packing.LANES * 4
full_bytes = slowmo.make_state_pack_spec(
    dataclasses.replace(slowmo.preset("local_sgd+slowmo", num_workers=W), packed=True),
    hlo_params).rows("float32") * packing.LANES * 4
assert 2 * shard_bytes == full_bytes, (shard_bytes, full_bytes)  # bytes ∝ 1/TP

# the census passing above proves the HLO matches the contract; these pin the
# CONTRACT to the three-level shape (axes + local-shard bytes)
by_name = {}
for bgt in ct.budgets:
    by_name.setdefault(bgt.name, []).append(bgt)
assert set(by_name) == {"pod-grad-sync", "boundary-average", "loss-pmean"}
# per inner step ONE packed gradient all-reduce over 'data' only, moving the
# LOCAL SHARD buffer
(grad,) = by_name["pod-grad-sync"]
assert grad.axes == ("data",) and len(grad.sizes) == TAU, grad
assert all(s == shard_bytes for s in grad.sizes), (grad, shard_bytes)
# per boundary ONE packed all-reduce over 'pod' only, local shard buffer
(boundary,) = by_name["boundary-average"]
assert boundary.axes == ("pod",) and boundary.sizes == (shard_bytes,), boundary
assert ct.boundary_bytes == shard_bytes == full_bytes // TP
# the loss's model-axis psums land in the tp-loss allowance: re-census with
# the allowance capped below the shard buffer — they must be activation-sized
(allowance,) = ct.allowances
assert allowance.axes == ("model",), allowance
violations = rules.check_census(
    contract_mod.round_contract(
        dataclasses.replace(
            slowmo.preset("local_sgd+slowmo", num_workers=W, tau=TAU),
            packed=True, unroll_inner=True),
        tp_layout, pack=pk, model_collective_max_bytes=shard_bytes - 1),
    MESH, txt)
assert not violations, [v.as_dict() for v in violations[:5]]
print("TP-HLO-OK all-reduce budgets: "
      f"data x{len(grad.sizes)}, pod x{len(boundary.sizes)}, "
      f"model allowance capped; boundary {shard_bytes} B = full/{TP}")

# gossip permutes stay pod-level: check_census pins every permute pair to the
# hop set, which on this mesh is exactly the same-(data, model)-index
# cross-pod pairs — verify that identity
ct_sgp, _, _ = audit_structure("sgp+slowmo", TAU)
hop_pairs = contract_mod.gossip_hop_pairs(
    tp_layout, slowmo.preset("sgp+slowmo", num_workers=W, tau=TAU))
ids = np.vectorize(lambda d: d.id)(MESH.devices)
pod_pairs = {(int(ids[p, d, m]), int(ids[(p + 1) % PODS, d, m]))
             for p in range(PODS) for d in range(DP) for m in range(TP)}
assert set(hop_pairs) == pod_pairs, (sorted(hop_pairs), sorted(pod_pairs))
assert any(b.op == "collective-permute" for b in ct_sgp.budgets)
print("TP-CP-OK gossip permutes pinned to", len(pod_pairs), "pod-level pairs")

# --- one rule, both paths ---------------------------------------------------
cfg_t = slowmo.preset("local_sgd+slowmo", num_workers=W, tau=2)
state_shapes = jax.eval_shape(lambda: slowmo.init_slowmo(cfg_t, params0))
dry = sharding.slowmo_state_specs(tp_layout, state_shapes)
mesh_specs = sharding.spmd_state_specs(tp_layout, state_shapes, exact_average=True)
for (pa, a), b in zip(jax.tree_util.tree_flatten_with_path(dry)[0],
                      jax.tree.leaves(mesh_specs)):
    assert a == b, (jax.tree_util.keystr(pa), a, b)
# flatten order of the dict is sorted: b, b0, w_down, w_in
pl = jax.tree.leaves(mesh_specs.params)
assert pl[2] == P("pod", "model", None), pl  # w_down: row-parallel (dim 0)
assert pl[3] == P("pod", None, "model"), pl  # w_in: column-parallel (dim 1)
assert pl[0] == P("pod", None) and pl[1] == P("pod", None), pl  # biases replicated
batch_shapes = {"x": jax.ShapeDtypeStruct((2, W, B, D), jnp.float32)}
gspmd = sharding.batch_shardings(tp_layout, batch_shapes)
mapped = sharding.spmd_batch_specs(tp_layout, batch_shapes)
assert gspmd["x"].spec == mapped["x"] == P(None, "pod", "data")  # model-replicated
print("TP-SPEC-UNIFY-OK")
print("ALL-OK")
"""


def test_tp_matches_tp_free_oracle_and_hlo_pins():
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
    assert proc.stdout.count("TP-EQ-OK") == 8
    assert "TP-HLO-OK" in proc.stdout
    assert "TP-CP-OK" in proc.stdout
    assert "TP-SPEC-UNIFY-OK" in proc.stdout
