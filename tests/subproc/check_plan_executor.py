"""Multi-device checks for the IR-interpreting executor and the
collective-matmul custom_vjp — run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8
(tests/test_plan_ir_exec.py drives it).

Contracts (ISSUE 3):
  * ``StagedCollectiveEngine`` executes by interpreting the CollectivePlan
    IR; its AG/RS outputs stay BIT-identical to the XLA one-shot
    collectives in every mode (AR exact here too: integer-valued inputs);
  * ``execute_plan`` run directly on an engine plan equals the engine;
  * the same plan object lowers through ``schedule_from_ir`` and passes
    the conflict-checked optical simulator;
  * ``allgather_matmul`` / ``matmul_reduce_scatter`` gradients (custom_vjp,
    fused-ring backward) match the unfused XLA composition's gradients.

Contracts (ISSUE 5, cross-world order search + hybrid execution):
  * on an asymmetric links table, ``PlanPolicy(order="optical")`` picks a
    DIFFERENT stage order than ``order="electrical"`` with strictly lower
    simulated Eq.-3 time, and the executor runs that exact plan
    bit-identically to the XLA one-shot collectives;
  * the ``hybrid`` mode (chunk wavefront over per-hop ring stages) stays
    bit-identical too, in both stage orders.

Contracts (ISSUE 8, latency-regime exchange plans):
  * decode-size payloads auto-plan recursive-doubling exchange chains and
    the exchange executor runs them bit-identically to the XLA one-shot
    collectives — auto pick AND forced ``regime="latency"`` — with the
    executed plan's optical price equal to the conflict-checked simulator.
"""
import os

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", ""), (
    "run me via tests/test_plan_ir_exec.py"
)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import shard_map
from repro.comms import StagedCollectiveEngine, execute_plan, make_factorized_mesh
from repro.core import TERARACK, price, schedule_from_ir
from repro.kernels.collective_matmul import allgather_matmul, matmul_reduce_scatter
from repro.optics import simulate

checks = []


def check(name, got, want, atol=0.0, exact=False):
    got = np.asarray(got)
    want = np.asarray(want)
    ok = got.shape == want.shape and (
        np.array_equal(got, want) if exact else np.allclose(got, want, atol=atol)
    )
    checks.append((name, ok))
    if not ok:
        print(f"FAIL {name}: shapes {got.shape} vs {want.shape}")
        print(" got ", got.ravel()[:8])
        print(" want", want.ravel()[:8])


mesh = make_factorized_mesh([2, 4], ["a", "b"])
names = ("a", "b")
eng = StagedCollectiveEngine(mesh, names)

x = jnp.arange(64, dtype=jnp.float32)
xs = jax.device_put(x, NamedSharding(mesh, P(names)))

# ---- engine (IR-interpreting) vs XLA one-shot, every mode -----------------
for mode in (None, "oneshot", "chunked", "perhop"):
    tag = mode or "planned"
    check(f"engine ag {tag}", eng.all_gather(xs, mode=mode), x, exact=True)
    check(f"engine rs {tag}", eng.reduce_scatter(x, mode=mode), 8 * x,
          exact=True)
    check(f"engine ar {tag}", eng.all_reduce(x, mode=mode), 8 * x, exact=True)

# ---- execute_plan on the engine's own plan == the engine ------------------
plan_ag = eng.plan(x, "ag")
direct = shard_map(
    lambda y: execute_plan(y, plan_ag), mesh=mesh,
    in_specs=P(names), out_specs=P(),
)(xs)
check("execute_plan direct == engine", direct, eng.all_gather(xs), exact=True)

# ---- the SAME plan object validates in the optical simulator --------------
for coll in ("ag", "rs", "ar"):
    plan = eng.plan(x, coll)
    sched = schedule_from_ir(plan, TERARACK.wavelengths)
    rep = simulate(sched, TERARACK, plan.shard_bytes, check=True)
    po = price(plan, TERARACK)
    check(f"plan {coll} price==sim", po.total_s, rep.time_s)
    check(f"plan {coll} steps", po.steps, rep.steps, exact=True)

# ---- collective-matmul custom_vjp vs unfused XLA composition --------------
key = jax.random.PRNGKey(0)
S, D, F = 16, 6, 10
xr = jax.random.normal(key, (S, D))
w1 = jax.random.normal(jax.random.PRNGKey(1), (D, F))
w2 = jax.random.normal(jax.random.PRNGKey(2), (D, F))


def ag_loss(fused):
    def inner(xs_, w1_, w2_):
        if fused:
            g, (o1, o2) = allgather_matmul(xs_, (w1_, w2_), names)
        else:
            g = lax.all_gather(xs_, names, axis=0, tiled=True)
            o1, o2 = g @ w1_, g @ w2_
        return (jnp.sum(o1 * o1) + jnp.sum(o2) + 3.0 * jnp.sum(g)) / 100.0

    def loss(x_, w1_, w2_):
        return shard_map(inner, mesh=mesh, in_specs=(P(names), P(), P()),
                         out_specs=P())(x_, w1_, w2_).mean()

    return jax.grad(loss, argnums=(0, 1, 2))(xr, w1, w2)


gf, gr = ag_loss(True), ag_loss(False)
for i, tag in enumerate(("dx", "dw1", "dw2")):
    check(f"ag_matmul vjp {tag}", gf[i], gr[i], atol=1e-5)

h = jax.random.normal(jax.random.PRNGKey(3), (S, D))
wr = jax.random.normal(jax.random.PRNGKey(4), (D, F))


def rs_loss(fused):
    def inner(h_, w_):
        if fused:
            y = matmul_reduce_scatter(h_, w_, names)
        else:
            y = lax.psum_scatter(h_ @ w_, names, scatter_dimension=0,
                                 tiled=True)
        return jnp.sum(y * y) / 100.0

    def loss(h_, w_):
        return shard_map(inner, mesh=mesh, in_specs=(P(), P()),
                         out_specs=P())(h_, w_).mean()

    return jax.grad(loss, argnums=(0, 1))(h, wr)


gf, gr = rs_loss(True), rs_loss(False)
for i, tag in enumerate(("dh", "dw")):
    check(f"mm_rs vjp {tag}", gf[i], gr[i], atol=1e-5)

# ---- model layer: SP-FFN fused fwd+grad vs the unfused staged path --------
from repro.models.mlp import ffn_apply_tp_sp

meshf = make_factorized_mesh([8], ["tp"])
B, S2, D2, F2 = 2, 16, 8, 16
pf = {"gate": {"w": jax.random.normal(jax.random.PRNGKey(5), (D2, F2 // 8))},
      "up": {"w": jax.random.normal(jax.random.PRNGKey(6), (D2, F2 // 8))},
      "down": {"w": jax.random.normal(jax.random.PRNGKey(7), (F2 // 8, D2))}}
xf = jax.random.normal(jax.random.PRNGKey(8), (B, S2, D2))


def ffn_grads(fuse):
    f = shard_map(
        lambda xs, pp: ffn_apply_tp_sp(pp, xs, ("tp",), fuse=fuse),
        mesh=meshf, in_specs=(P(None, "tp"), P()), out_specs=P(None, "tp"))

    def loss(x_, pp):
        return jnp.sum(f(x_, pp) ** 2)

    return jax.value_and_grad(loss, argnums=(0, 1))(xf, pf)


(vf, gf), (vr, gr) = ffn_grads(True), ffn_grads(False)
check("ffn_tp_sp fused loss", vf, vr, atol=1e-4)
check("ffn_tp_sp dx", gf[0], gr[0], atol=1e-4)
for k in ("gate", "up", "down"):
    check(f"ffn_tp_sp dw[{k}]", gf[1][k]["w"], gr[1][k]["w"], atol=1e-4)

# ---- api fused ops OUTSIDE shard_map, rank-3 activations ------------------
# regression: the outside-path out_specs must shard the OUTPUT's feature
# dim (last of x's rank), not mirror the rank-2 weight layout
from repro.comms.api import (
    allgather_matmul as api_agmm,
    comm_context,
    matmul_reduce_scatter as api_mmrs,
)

with comm_context(mesh, names):
    x3 = jnp.arange(2 * 8 * 4, dtype=jnp.float32).reshape(2, 8, 4)
    w3 = (jnp.arange(4 * 16, dtype=jnp.float32).reshape(4, 16) % 5) - 2
    g3, o3 = api_agmm(x3, w3, axis=1)
    check("api ag_matmul rank3 gathered", g3, x3, exact=True)
    check("api ag_matmul rank3 out", o3, x3 @ w3, exact=True)
    h3 = jnp.arange(2 * 8 * 16, dtype=jnp.float32).reshape(2, 8, 16) % 7
    w3r = (jnp.arange(16 * 4, dtype=jnp.float32).reshape(16, 4) % 3) - 1
    check("api mm_rs rank3", api_mmrs(h3, w3r, axis=1), h3 @ w3r, exact=True)

# ---- explicit-TP transformer block vs the GSPMD block (ISSUE 4) -----------
# Two weight constructions: x entries are ±1, positions are 0 (RoPE is the
# identity), and either the row-parallel weights (wo, down) are zero outside
# shard 0's rows — every cross-shard reduction adds exact 0.0s — or they
# are fully dense.  Neither is compared bit for bit: XLA (0.9) fuses the
# block differently when it is partitioned, so even its own GSPMD
# partitioning of the reference block differs from the unpartitioned block
# in the last float32 bits (-149.72191 vs -149.7219).  The block is
# compared as float32 arithmetic in another order: the largest difference
# must stay within SCALED_RTOL of the largest activation.  Integer weights
# drive activations to ~1e3, where the observed differences are ~1e-3
# (dense) and ~1e-5 (shard-0 rows); a wrong head split, combine or layout
# moves outputs by the activations' own size.
SCALED_RTOL = 1e-5


def check_scaled(name, got, want):
    got, want = np.asarray(got), np.asarray(want)
    ok = got.shape == want.shape and (
        np.max(np.abs(got - want)) <= SCALED_RTOL * np.max(np.abs(want)))
    checks.append((name, ok))
    if not ok:
        print(f"FAIL {name}: shapes {got.shape} vs {want.shape}, max diff "
              f"{np.max(np.abs(got - want))} vs scale {np.max(np.abs(want))}")
import dataclasses

from repro.comms.api import comm_context
from repro.configs import ModelConfig
from repro.models.model import (
    _layer_init,
    transformer_block_ref,
    transformer_block_tp,
    tp_block_specs,
)

cfg_tp = ModelConfig(
    name="check-tp-block", family="dense", dtype="float32", remat=False,
    qkv_bias=False, qk_norm=False, num_layers=2, d_model=32, num_heads=8,
    num_kv_heads=8, head_dim=8, d_ff=64, vocab_size=64,
)
NTP = 8
B, ST = 2, 16  # seq divisible by the 8 devices (SP shards the seq axis)
key = jax.random.PRNGKey(9)


def int_weights(layer, *, shard0_rows: bool):
    """Integer-valued params; with ``shard0_rows`` the row-parallel weights
    (wo, down) keep only shard 0's row block."""
    import zlib

    def intify(path, leaf):
        keys = [getattr(k, "key", None) for k in path]
        # crc32, not hash(): str hashing is PYTHONHASHSEED-randomized and
        # would draw different weights every run
        seed = zlib.crc32("/".join(str(k) for k in keys).encode())
        a = jnp.round(
            2.0 * jax.random.normal(
                jax.random.fold_in(jax.random.PRNGKey(11), seed % (2**31)),
                leaf.shape)
        ).astype(jnp.float32)
        if "scale" in keys:
            return jnp.ones_like(leaf)
        if shard0_rows and leaf.ndim == 2 and any(k in ("wo", "down") for k in keys):
            rows = leaf.shape[0] // NTP
            mask = (jnp.arange(leaf.shape[0]) < rows)[:, None]
            a = jnp.where(mask, a, 0.0)
        return a

    return jax.tree_util.tree_map_with_path(intify, layer)


layer0 = _layer_init(key, cfg_tp, dtype=jnp.float32)
x_pm1 = jnp.where(
    jax.random.bernoulli(jax.random.PRNGKey(12), 0.5, (B, ST, cfg_tp.d_model)),
    1.0, -1.0).astype(jnp.float32)
pos0 = jnp.zeros((B, ST), jnp.int32)

mesh_tp = make_factorized_mesh([2, 4], ["ta", "tb"])
names_tp = ("ta", "tb")

for shard0, tag in ((True, "shard0-rows"), (False, "dense")):
    layer_tp = int_weights(layer0, shard0_rows=shard0)
    ref = jax.jit(lambda lx, ll: transformer_block_ref(
        ll, cfg_tp, lx, positions=pos0))(x_pm1, layer_tp)
    with comm_context(mesh_tp, names_tp) as ctx_tp:
        for sp in (False, True):
            x_spec, l_spec = tp_block_specs(
                layer_tp, names_tp, sequence_parallel=sp)
            fn = jax.jit(shard_map(
                lambda lx, ll, sp=sp: transformer_block_tp(
                    ll, cfg_tp, lx, positions=pos0, sequence_parallel=sp),
                mesh=mesh_tp, in_specs=(x_spec, l_spec), out_specs=x_spec,
            ))
            check_scaled(f"tp_block {tag} sp={sp}", fn(x_pm1, layer_tp), ref)
        # the GSPMD path proper: jit partitions the reference block from
        # TP shardings
        if shard0:
            from jax.sharding import NamedSharding

            x_spec, l_spec = tp_block_specs(layer_tp, names_tp)
            gspmd = jax.jit(
                lambda lx, ll: transformer_block_ref(
                    ll, cfg_tp, lx, positions=pos0),
                in_shardings=(
                    NamedSharding(mesh_tp, x_spec),
                    jax.tree.map(lambda s: NamedSharding(mesh_tp, s), l_spec),
                ),
                out_shardings=NamedSharding(mesh_tp, x_spec),
            )
            check_scaled("tp_block gspmd-partitioned", gspmd(x_pm1, layer_tp),
                     ref)
    assert ctx_tp.cache_stats.misses > 0  # the block planned via the context

# ---- ISSUE 5: optical stage-order search + hybrid execution ---------------
# Asymmetric links: the size-4 axis rides the SLOW transport.  The
# electrical planner puts it first for the all-gather (smallest payload on
# the slow link); the optical Eq.-3/RWA pricer at w=2 prefers running its
# ring hops as stage 1 (whole-ring wavelength reuse) — a strictly cheaper,
# strictly different order.  The executor must run BOTH plans (and the new
# hybrid mode) bit-identically to the XLA one-shot collectives.
import dataclasses as _dc

from repro.comms.api import PlanPolicy, all_gather, all_reduce, reduce_scatter
from repro.comms.api import CommContext
from repro.core.planner import LinkSpec

ASYM_LINKS = {"a": LinkSpec("fast", 50e9, 1e-6),
              "b": LinkSpec("slow", 1e9, 1e-5)}
SYS_W2 = _dc.replace(TERARACK, n_nodes=8, wavelengths=2)
ctx_elec = CommContext(mesh, names, links=ASYM_LINKS,
                       policy=PlanPolicy(order="electrical", optical=SYS_W2))
ctx_opt = CommContext(mesh, names, links=ASYM_LINKS,
                      policy=PlanPolicy(order="optical", optical=SYS_W2))

xb = jnp.arange(2**14, dtype=jnp.float32)  # 64 KiB: big enough to chunk
xbs = jax.device_put(xb, NamedSharding(mesh, P(names)))
shard_b = xb.size * xb.dtype.itemsize / 8

for coll in ("ag", "rs", "ar"):
    pe = ctx_elec.plan(coll, shard_b, shape=tuple(xb.shape), dtype=xb.dtype)
    po = ctx_opt.plan(coll, shard_b, shape=tuple(xb.shape), dtype=xb.dtype)
    srch = po.meta["order_search"]
    checks.append((f"order {coll} flipped", pe.axes != po.axes
                   and srch["flipped"]))
    # the optical pick is STRICTLY cheaper under Eq. 3 (not a tie-break)
    checks.append((
        f"order {coll} optical strictly cheaper",
        price(po, SYS_W2).total_s < price(pe, SYS_W2).total_s,
    ))
    # price == simulate for the winner, conflict-checked
    rep = simulate(schedule_from_ir(po, SYS_W2.wavelengths), SYS_W2,
                   po.shard_bytes, check=True)
    checks.append((f"order {coll} price==sim",
                   abs(rep.time_s - price(po, SYS_W2).total_s) < 1e-12))

# both contexts' searched plans execute bit-identically to XLA, in the
# planned mode AND forced hybrid (chunk wavefront over ring stages)
for tag, ctx_i in (("elec", ctx_elec), ("optical", ctx_opt)):
    for mode, chunks in ((None, None), ("hybrid", 2), ("hybrid", 4)):
        mtag = f"{tag}/{mode or 'planned'}" + (f"x{chunks}" if chunks else "")
        check(f"order ag {mtag}",
              all_gather(xbs, ctx=ctx_i, mode=mode, num_chunks=chunks),
              xb, exact=True)
        check(f"order rs {mtag}",
              reduce_scatter(xb, ctx=ctx_i, mode=mode, num_chunks=chunks),
              8 * xb, exact=True)
        check(f"order ar {mtag}",
              all_reduce(xb, axis=0, ctx=ctx_i, mode=mode, num_chunks=chunks),
              8 * xb, exact=True)

# hybrid via the default (symmetric-links) engine too: planned mode at this
# size may already BE hybrid; force a chunked wavefront explicitly as well
check("engine ag hybrid", eng.all_gather(xs, mode="hybrid"), x, exact=True)
check("engine rs hybrid", eng.reduce_scatter(x, mode="hybrid"), 8 * x,
      exact=True)
check("engine ar hybrid", eng.all_reduce(x, mode="hybrid"), 8 * x,
      exact=True)

# ---- ISSUE 6: all-to-all as a first-class collective ----------------------
# api.all_to_all must stay BIT-identical to the XLA one-shot
# lax.all_to_all in every plan mode (the staged digit-transposes commute,
# the ring stages restore origin order exactly), and the expert-parallel
# MoE dispatch must cross the mesh through it.
from repro.comms.api import all_to_all as api_a2a

xa = jnp.arange(8 * 16, dtype=jnp.float32)
xa_want = shard_map(
    lambda y: lax.all_to_all(y, names, 0, 0, tiled=True), mesh=mesh,
    in_specs=P(names), out_specs=P(names))(xa)
with comm_context(mesh, names) as ctx_a2a:
    for mode, chunks in ((None, None), ("oneshot", None), ("chunked", 4),
                         ("perhop", None), ("hybrid", 2)):
        mtag = (mode or "planned") + (f"x{chunks}" if chunks else "")
        check(f"a2a {mtag}",
              api_a2a(xa, ctx=ctx_a2a, mode=mode, num_chunks=chunks),
              xa_want, exact=True)
    checks.append(("a2a planned via context cache",
                   any(pl.collective == "a2a" for pl in ctx_a2a.plans())))

# a2a order search: electrical cost is stage-order invariant, so the flip
# is tie-break vs strict optical preference; 2x4 ties optically — the 2x3
# table at w=2 separates (6 vs 7 RWA steps).  Meshless context: no devices.
ctx_a2a_o = CommContext(
    axis_names=("a", "b"), links=ASYM_LINKS, axis_sizes={"a": 2, "b": 3},
    policy=PlanPolicy(order="optical",
                      optical=_dc.replace(TERARACK, n_nodes=6, wavelengths=2)))
po6 = ctx_a2a_o.plan("a2a", 6 * 1024.0)
srch6 = po6.meta["order_search"]
checks.append(("a2a order flipped", srch6["flipped"]
               and po6.axes == ("b", "a")))
from repro.core import optical_message_bytes

SYS6 = _dc.replace(TERARACK, n_nodes=6, wavelengths=2)
rep6 = simulate(schedule_from_ir(po6, 2), SYS6,
                optical_message_bytes(po6), check=True)
checks.append(("a2a order price==sim",
               abs(rep6.time_s - price(po6, SYS6).total_s) < 1e-12))

# ---- MoE expert-parallel dispatch through api.all_to_all ------------------
from repro.configs import MoEConfig, expert_parallel
from repro.models.moe import moe_block, moe_init

mesh_ep = make_factorized_mesh([8], ["ep"])
cfg_moe = ModelConfig(
    name="check-moe-ep", family="moe", dtype="float32", remat=False,
    num_layers=2, d_model=16, num_heads=2, num_kv_heads=2, head_dim=8,
    d_ff=32, vocab_size=64,
    moe=MoEConfig(num_experts=16, top_k=2, d_ff_expert=24,
                  shared_expert=True))
cfg_ep = expert_parallel(cfg_moe, axis="ep")
p_moe = moe_init(jax.random.PRNGKey(13), cfg_ep, dtype=jnp.float32)
x_moe = jax.random.normal(jax.random.PRNGKey(14), (16, 4, 16), jnp.float32)
# group-local dispatch never crosses shards: the EP block must equal the
# all-experts-local block run per device shard
ref_moe = jnp.concatenate(
    [moe_block(p_moe, cfg_moe, x_moe[i * 2:(i + 1) * 2])[0]
     for i in range(8)], axis=0)
with comm_context(mesh_ep, ("ep",)) as ctx_ep:
    got_moe = jax.jit(shard_map(
        lambda pp, xx: moe_block(pp, cfg_ep, xx)[0], mesh=mesh_ep,
        in_specs=(P(), P("ep")), out_specs=P("ep")))(p_moe, x_moe)
    # the a2a moves tokens exactly, but under XLA 0.9 the EP block's expert
    # matmuls (over (E/8)-expert buffers) round differently from the local
    # block's in the last float32 bit: bit-exact comparison fails at the
    # parent tree too, with max diff 1.16e-10 against a largest output of
    # 1.07e-3 (1.1e-7 of scale; 8 CPU devices, eager, jitted and
    # shard_map'd local references alike).  Same rule as the TP block.
    check_scaled("moe ep == local reference", got_moe, ref_moe)
    checks.append(("moe ep issued a2a plans",
                   any(pl.collective == "a2a" for pl in ctx_ep.plans())
                   and ctx_ep.cache_stats.hits > 0))

# ---- ISSUE 8: latency-regime exchange execution ---------------------------
# Decode-size payloads auto-plan recursive-doubling exchange chains; the
# exchange executor must run them BIT-identically to the XLA one-shot
# collectives on the 8-device mesh, for the auto pick AND the forced
# regime="latency" policy, and the executed plan's optical price must be
# the conflict-checked simulator's wall time.
ctx_auto8 = CommContext(mesh, names, links=ASYM_LINKS)
ctx_lat8 = CommContext(mesh, names, links=ASYM_LINKS,
                       policy=PlanPolicy(regime="latency"))

x_sm = jnp.arange(256, dtype=jnp.float32)  # 1 KiB total: 128 B shards
x_sms = jax.device_put(x_sm, NamedSharding(mesh, P(names)))
shard_sm = x_sm.size * x_sm.dtype.itemsize / 8

p_auto = ctx_auto8.plan("ar", shard_sm, shape=tuple(x_sm.shape),
                        dtype=x_sm.dtype)
checks.append(("regime auto picks latency at decode size",
               p_auto.meta["regime"] == "latency"
               and all(s.mode == "exchange" for s in p_auto.stages)))
xov8 = ctx_auto8.latency_crossover("ar")
checks.append(("regime crossover bounds the auto pick",
               xov8 is not None and shard_sm < xov8))

for tag, ctx_i in (("auto", ctx_auto8), ("forced", ctx_lat8)):
    check(f"exchange ag {tag}", all_gather(x_sms, ctx=ctx_i), x_sm,
          exact=True)
    check(f"exchange rs {tag}", reduce_scatter(x_sm, ctx=ctx_i), 8 * x_sm,
          exact=True)
    check(f"exchange ar {tag}", all_reduce(x_sm, axis=0, ctx=ctx_i),
          8 * x_sm, exact=True)

for coll in ("ag", "rs", "ar"):
    pl8 = ctx_lat8.plan(coll, shard_sm, shape=tuple(x_sm.shape),
                        dtype=x_sm.dtype)
    checks.append((f"exchange {coll} all-exchange stages",
                   all(s.mode == "exchange" for s in pl8.stages)))
    rep8 = simulate(schedule_from_ir(pl8, SYS_W2.wavelengths), SYS_W2,
                    optical_message_bytes(pl8), check=True)
    checks.append((f"exchange {coll} price==sim",
                   abs(rep8.time_s - price(pl8, SYS_W2).total_s) < 1e-12))
checks.append(("regime cache counters split",
               ctx_lat8.cache_stats.latency_plans == 3
               and ctx_auto8.cache_stats.latency_plans >= 1))

# ---------------------------------------------------------------------------
failed = [n for n, ok in checks if not ok]
print(f"{len(checks) - len(failed)}/{len(checks)} checks passed")
if failed:
    raise SystemExit(f"FAILED: {failed}")
print("PLAN-EXECUTOR-OK")
