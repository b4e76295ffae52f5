"""Capacity-planner tests: the §2.3.1 deployment calculation."""
import dataclasses

import pytest

from repro.configs.registry import get_config
from repro.core.capacity_planner import (expected_active_per_layer, plan)
from repro.simulator.hardware import PLATFORMS, platform_for_device_kind


def test_expected_active_monotone_in_batch():
    cfg = get_config("deepseek-v2-lite")
    a1 = expected_active_per_layer(cfg, 1)
    a8 = expected_active_per_layer(cfg, 8)
    a64 = expected_active_per_layer(cfg, 64)
    assert a1 <= a8 <= a64 <= cfg.moe.num_experts
    assert a1 >= cfg.moe.top_k * 0.9


def test_concentration_reduces_demand():
    cfg = get_config("qwen2-moe-57b")
    spread = expected_active_per_layer(cfg, 32, concentration=1.0)
    tight = expected_active_per_layer(cfg, 32, concentration=0.3)
    assert tight < spread


def test_plan_deepseek_on_a6000_20GB():
    """The paper's setting: DeepSeek-V2-Lite on a 20 GB budget."""
    cfg = get_config("deepseek-v2-lite")
    p = plan(cfg, PLATFORMS["a6000"], memory_budget_bytes=20e9, batch=8,
             kv_len=1024)
    assert 0 < p.capacity_experts < p.total_experts  # memory-constrained
    assert 0.2 < p.resident_fraction < 0.9
    assert 1 <= p.s_initial <= 12
    assert p.expert_bytes == pytest.approx(3 * 2048 * 1408 * 2)


def test_plan_infeasible_on_slow_link():
    """An 8 GB/s link with a tiny budget cannot hide transfers: the plan
    must say so rather than promising a working S."""
    cfg = get_config("qwen2-moe-57b")
    p = plan(cfg, PLATFORMS["rx6500xt"], memory_budget_bytes=6e9, batch=16,
             kv_len=2048)
    assert p.resident_fraction < 0.2
    assert not p.bandwidth_feasible
    assert p.expected_stall_per_layer_s > 0


def test_bigger_budget_more_slots():
    cfg = get_config("qwen1.5-moe-a2.7b")
    small = plan(cfg, PLATFORMS["a6000"], memory_budget_bytes=10e9)
    big = plan(cfg, PLATFORMS["a6000"], memory_budget_bytes=30e9)
    assert big.capacity_experts > small.capacity_experts
    assert big.expected_stall_per_layer_s <= small.expected_stall_per_layer_s


def test_faster_link_smaller_s():
    cfg = get_config("deepseek-v2-lite")
    slow = plan(cfg, PLATFORMS["rtx4090"], memory_budget_bytes=20e9)
    fast = plan(cfg, PLATFORMS["h20"], memory_budget_bytes=20e9)
    # S = N_e*E_s/(C_s*T_l): same T_l model, 4x bandwidth -> smaller-or-equal S
    assert fast.s_initial <= slow.s_initial
    assert fast.summary()  # renders


def test_device_kind_maps_to_spec_and_unknown_is_an_error():
    assert platform_for_device_kind("TPU v5 lite") is PLATFORMS["tpu_v5e"]
    with pytest.raises(ValueError, match="TPU v99"):
        platform_for_device_kind("TPU v99")
