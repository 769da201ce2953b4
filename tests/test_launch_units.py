"""Unit tests: HLO collective parser, roofline terms, optimizer schedule."""
from __future__ import annotations

import jax.numpy as jnp

from repro.launch.hlo_analysis import parse_collectives, roofline_terms
from repro.train.optim import AdamWConfig, lr_schedule

HLO = """
ENTRY %main {
  %ag = bf16[16,4096]{1,0} all-gather(%p0), replica_groups=[16,16]<=[256], dimensions={0}
  %ar = f32[1024]{0} all-reduce(%p1), replica_groups={{0,1,2,3}}, to_apply=%add
  %rs = f32[64]{0} reduce-scatter(%p2), replica_groups=[32,8]<=[256], dimensions={0}
  %cp = bf16[128,128]{1,0} collective-permute(%p3), source_target_pairs={{0,1}}
  %ags = (bf16[8,8]{1,0}, bf16[64,8]{1,0}) all-gather-start(%p4), replica_groups=[32,8]<=[256]
}
"""


def test_parse_collectives_counts_and_groups():
    st = parse_collectives(HLO, n_devices=256)
    assert st.counts == {"all-gather": 2, "all-reduce": 1,
                         "reduce-scatter": 1, "collective-permute": 1}
    assert st.by_group_size["all-gather/16"] == 1
    assert st.by_group_size["all-reduce/4"] == 1
    assert st.by_group_size["reduce-scatter/8"] == 1


def test_parse_collectives_wire_bytes():
    st = parse_collectives(HLO, n_devices=256)
    ag = 16 * 4096 * 2 * (15 / 16)              # result x (n-1)/n
    ar = 2 * 1024 * 4 * (3 / 4)
    rs = 64 * 4 * 7                              # shard result x (n-1)
    cp = 128 * 128 * 2
    ags = (8 * 8 + 64 * 8) * 2 // 2 * (7 / 8)    # tuple: half is the result
    assert abs(st.wire_bytes - (ag + ar + rs + cp + ags)) < 1.0


def test_roofline_terms_dominance():
    r = roofline_terms(flops=197e12, bytes_accessed=819e9 * 2,
                       wire_bytes=50e9 * 0.5, peak_flops=197e12,
                       hbm_bw=819e9, link_bw=50e9)
    assert abs(r["compute_s"] - 1.0) < 1e-9
    assert abs(r["memory_s"] - 2.0) < 1e-9
    assert r["dominant"] == "memory"
    assert abs(r["roofline_fraction"] - 0.5) < 1e-9


def test_chip_peaks_keyed_by_device_kind():
    import pytest

    from repro.launch.mesh import TARGET_KIND, chip_peaks

    v5e = chip_peaks(TARGET_KIND)
    assert (v5e.bf16_flops, v5e.int8_ops, v5e.hbm_bw, v5e.hbm_bytes) == (
        197e12, 393e12, 819e9, 16 * 10**9)
    with pytest.raises(KeyError, match="no published peaks"):
        chip_peaks("cpu")


def test_lr_schedule_shape():
    cfg = AdamWConfig(lr=1e-3, warmup_steps=10, decay_steps=100,
                      min_lr_ratio=0.1)
    lrs = [float(lr_schedule(cfg, jnp.asarray(s))) for s in
           (0, 5, 10, 55, 100, 1000)]
    assert lrs[0] == 0.0
    assert abs(lrs[1] - 5e-4) < 1e-9            # linear warmup
    assert abs(lrs[2] - 1e-3) < 1e-9            # peak at warmup end
    assert lrs[2] > lrs[3] > lrs[4]             # cosine decay
    assert abs(lrs[4] - 1e-4) < 1e-9            # floor = min_lr_ratio * lr
    assert abs(lrs[5] - 1e-4) < 1e-9            # clamped after decay_steps
