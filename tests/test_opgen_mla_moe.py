"""Latent attention and expert-parallel DeepSeekMoE in the operator trace
(``opgen.arch_workload``): the work the trace prices adds up to the
published model's, the expert-parallel shares add up to the uncut
layer, decode reads the one latent cache, and the dense and SSM traces
keep their ops."""
import math
import os
import sys

import jax
import pytest

from repro.configs import SHAPES, get_arch, list_archs
from repro.configs.base import ShapeConfig
from repro.core.opgen import _routed_experts, arch_workload
from repro.models.param import is_spec
from repro.models.registry import count_params, param_specs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)          # benchmarks.arch_power

DS = "deepseek-v2-236b"
# the deployment of the benchmark's configuration: 32 chips, tp=1
PREFILL = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODES = (ShapeConfig("decode_32k", 32768, 512, "decode"),
           ShapeConfig("decode_128k", 131072, 128, "decode"))
PROJECTIONS = {"q_a", "kv_a", "q_b", "kv_b", "o_proj", "mlp_up", "mlp_down",
               "router", "expert_up", "expert_down", "shared_up",
               "shared_down"}
EXPERT = {"expert_up", "expert_down"}
NEW = PROJECTIONS - {"mlp_up", "mlp_down"} | {
    "mla_norm_rope", "q_absorb", "mla_decode", "o_absorb", "router_topk",
    "a2a_dispatch", "a2a_combine", "expert_swiglu", "shared_swiglu"}


def _sum(wl, names, attr="flops_sa") -> int:
    return sum(int(getattr(o, attr)) * o.count for o in wl.ops
               if o.name in names)


def _weight_bytes(wl, names) -> int:
    return sum(o.matmul_dims[1] * o.matmul_dims[2] * 2 * o.count
               for o in wl.ops if o.name in names)


def _projection_params(cfg) -> int:
    """Active parameters less the embedding, the output head and the
    norms: the weights every token multiplies by."""
    leaves = jax.tree_util.tree_flatten_with_path(param_specs(cfg),
                                                  is_leaf=is_spec)[0]
    skip = sum(math.prod(leaf.shape) for path, leaf in leaves
               if path[-1].key in ("embed", "lm_head", "ln", "final_norm")
               or path[-1].key.endswith("_norm"))
    return count_params(cfg, active_only=True) - skip


def test_prefill_projection_flops_are_twice_the_active_parameters():
    cfg = get_arch(DS)
    wl = arch_workload(cfg, PREFILL, n_chips=32, tp=1)
    tokens = PREFILL.global_batch * PREFILL.seq_len
    assert _sum(wl, PROJECTIONS) * 32 == 2 * _projection_params(cfg) * tokens


@pytest.mark.parametrize("shape", [
    ShapeConfig("prefill_tiny", 64, 4, "prefill"),
    ShapeConfig("decode_tiny", 64, 16, "decode")])
def test_expert_parallel_shares_add_up_to_the_uncut_layer(shape):
    cfg = get_arch(DS).reduced()
    share = arch_workload(cfg, shape, n_chips=4, tp=1)
    whole = arch_workload(cfg, shape, n_chips=1, tp=1)
    # each of the 4 chips holds one expert and routes its quarter of the
    # tokens; the shared experts run on each chip's own tokens
    for names in (EXPERT, {"shared_up", "shared_down"}, {"router"}):
        assert 4 * _sum(share, names) == _sum(whole, names), names
    assert 4 * _weight_bytes(share, EXPERT) == _weight_bytes(whole, EXPERT)
    mo = cfg.moe
    assert _weight_bytes(whole, EXPERT) == (
        (cfg.n_layers - mo.first_dense_layers) * mo.n_experts
        * 3 * cfg.d_model * mo.d_ff_expert * 2)
    # the uncut layer exchanges nothing; the shares do
    assert _sum(whole, {"a2a_dispatch"}, "bytes_ici") == 0
    assert _sum(share, {"a2a_dispatch"}, "bytes_ici") > 0


@pytest.mark.parametrize("shape", DECODES, ids=lambda s: s.name)
def test_decode_reads_the_latent_cache_once_a_sequence(shape):
    cfg = get_arch(DS)
    wl = arch_workload(cfg, shape, n_chips=32, tp=1)
    att = [o for o in wl.ops if o.name == "mla_decode"]
    assert len(att) == cfg.n_layers
    seqs = shape.global_batch // 32
    for o in att:
        assert o.count == seqs
        assert o.bytes_hbm * o.count == seqs * shape.seq_len * 576 * 2
        assert o.matmul_dims == (128, 576, shape.seq_len)
        assert o.flops_vu == 0 and o.flops_sa > 0
    assert not any(o.name in ("attn_decode", "qkv") for o in wl.ops)


def test_decode_dispatch_and_combine_cross_the_ici():
    cfg = get_arch(DS)
    wl = arch_workload(cfg, DECODES[0], n_chips=32, tp=1)
    a2a = [o for o in wl.ops if o.name in ("a2a_dispatch", "a2a_combine")]
    assert len(a2a) == 2 * (cfg.n_layers - 1)
    # 16 tokens x top-6 slots of 5120 bf16, 31 of 32 chips away
    assert all(o.collective and o.bytes_ici == 16 * 6 * 5120 * 2 * 31 / 32
               for o in a2a)


@pytest.mark.parametrize("R,E", [(1, 5), (6, 5), (12, 5), (96, 5), (5, 5),
                                 (196608, 5), (7, 1), (3, 160)])
def test_routed_experts_price_each_expert_at_its_own_rows(R, E):
    D, F = 5120, 1536
    ops = _routed_experts(R, E, D, F)
    ups = [o for o in ops if o.name == "expert_up"]
    assert len(ups) <= 2
    assert sum(o.count for o in ups) == min(E, R)
    assert sum(o.matmul_dims[0] * o.count for o in ups) == R
    assert max(o.matmul_dims[0] for o in ups) - min(
        o.matmul_dims[0] for o in ups) <= 1
    assert sum(int(o.flops_sa) * o.count for o in ops) == 2 * R * 3 * D * F


@pytest.mark.parametrize("arch", list_archs())
def test_dense_and_ssm_traces_carry_none_of_the_new_ops(arch):
    cfg = get_arch(arch)
    names = {o.name for s in ("prefill_32k", "decode_32k")
             for o in arch_workload(cfg, SHAPES[s]).ops}
    if cfg.moe is None and cfg.mla is None:
        assert not names & NEW
    else:
        assert {"router", "a2a_dispatch", "expert_up"} <= names
        assert ("mla_decode" in names) == (cfg.mla is not None)


def test_arch_power_runs_every_registered_arch_and_shape():
    from benchmarks.arch_power import arch_power_table
    rows = arch_power_table()
    want = sum(st == "ok" for a in list_archs()
               for st in get_arch(a).supported_shapes().values())
    assert len(rows) == want
    assert any(r[0] == f"arch_save/{DS}/decode_32k" for r in rows)
