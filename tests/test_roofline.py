"""HLO roofline parser unit tests + a real tiny compile."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch import roofline as rl

SYNTH = """\
HloModule test

%cond.1 (arg.0: s32[]) -> pred[] {
  %arg.0 = s32[] parameter(0)
  %constant.5 = s32[] constant(12)
  ROOT %lt = pred[] compare(%arg.0, %constant.5), direction=LT
}

%body.1 (arg.1: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %arg.1 = (s32[], f32[8,16]) parameter(0)
  %w = f32[16,16]{1,0} constant({...})
  %x = f32[8,16]{1,0} get-tuple-element(%arg.1), index=1
  %d = f32[8,16]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,16]{1,0} all-reduce(%d), replica_groups={{0,1,2,3}}, to_apply=%sum.1
  ROOT %t = (s32[], f32[8,16]) tuple(%x, %ar)
}

%sum.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (p0: f32[8,16]) -> f32[8,16] {
  %p0 = f32[8,16]{1,0} parameter(0)
  %wl = (s32[], f32[8,16]) while(%tup), condition=%cond.1, body=%body.1
  %big = f32[32,64]{1,0} all-gather(%p0), replica_groups=[4,2]<=[8], dimensions={0}
  ROOT %r = f32[8,16]{1,0} get-tuple-element(%wl), index=1
}
"""


def test_shape_bytes():
    assert rl.shape_bytes("f32[8,16]{1,0}") == 8 * 16 * 4
    assert rl.shape_bytes("bf16[4,4]") == 32
    assert rl.shape_bytes("(s32[], f32[2,2]{1,0})") == 4 + 16
    assert rl.shape_bytes("pred[7]") == 7


def test_synthetic_module_trip_counts_and_collectives():
    ana = rl.analyze_hlo(SYNTH, num_devices=8)
    # dot inside while body: 2*8*16*16 flops x 12 trips
    assert ana["dot_flops"] == 2 * 8 * 16 * 16 * 12
    # all-reduce in body: 8*16*4 bytes x 12 x wire factor 2*(4-1)/4
    ar = ana["collective_wire_bytes"]["all-reduce"]
    assert ar == pytest.approx(8 * 16 * 4 * 12 * 2 * 3 / 4)
    # all-gather at entry: group size 2 from [4,2] v2 format
    ag = ana["collective_wire_bytes"]["all-gather"]
    assert ag == pytest.approx(32 * 64 * 4 * (2 - 1) / 2)


def test_real_compile_collectives_nonzero():
    """Compile a tiny sharded matmul on 1 device and parse its HLO."""
    x = jnp.ones((8, 8))

    def f(a):
        y = a @ a
        return jax.lax.scan(lambda c, _: (c @ a, None), y, None, length=5)[0]

    hlo = jax.jit(f).lower(x).compile().as_text()
    ana = rl.analyze_hlo(hlo, num_devices=1)
    # scan body dot must be multiplied by 5 (+1 for the outer matmul)
    assert ana["dot_flops"] >= 2 * 8 * 8 * 8 * 6


def test_roofline_terms_dominance():
    t = rl.roofline_terms(1e15, 1e9, 1e9, device_kind=rl.V5E)  # compute
    assert t["dominant"] == "compute" and t["roofline_fraction"] == 1.0
    t = rl.roofline_terms(1e12, 1e9, 1e12, device_kind=rl.V5E)  # collective
    assert t["dominant"] == "collective"
    assert t["roofline_fraction"] < 1.0


def test_roofline_peaks_unknown_device_kind_raises():
    """Peaks come from the published table only: a device kind that is not
    in it (the CPU backend, say) is an error, not a silent v5e default."""
    assert rl.peaks(rl.V5E)["hbm_bw"] == 819e9
    with pytest.raises(KeyError, match="cpu"):
        rl.roofline_terms(1.0, 1.0, 1.0, device_kind="cpu")
