"""Operations and bytes of ``paged_decode`` calls, as the ``gqa`` family
counts them, against counts worked out by hand at smollm-360m and
internlm2-1.8b widths."""
from benchmarks.chip import flops, spec

gqa = spec.family("gqa", "test_flops.py")
Shape = gqa.Shape

SMOLLM = Shape(layers=32, d_model=960, heads=15, kv_heads=5, head_dim=64,
               d_ff=2560, vocab=49152, padded_vocab=49152, tied=True,
               rope_theta=1e4, norm_eps=1e-6)
INTERNLM2 = Shape(layers=24, d_model=2048, heads=16, kv_heads=8,
                  head_dim=128, d_ff=8192, vocab=92544, padded_vocab=92672,
                  tied=False, rope_theta=1e6, norm_eps=1e-6)


def test_paged_decode_call_smollm():
    # two slots of 100 and 2000 valid tokens
    # ops: 4 * 15 heads * 64 * 2100 tokens = 8,064,000
    # bytes: K+V 2 * 5 * 64 * 2100 * 2 = 2,688,000; q + out 2 * 2 * 15 * 64
    #        * 2 = 7,680
    assert gqa.paged_decode_call(SMOLLM, [100, 2000]) == (8_064_000,
                                                          2_695_680)


def test_paged_decode_call_internlm2():
    # eight slots of 1000 tokens
    # ops: 4 * 16 * 128 * 8000 = 65,536,000
    # bytes: 2 * 8 * 128 * 8000 * 2 = 32,768,000; 2 * 8 * 16 * 128 * 2
    #        = 65,536
    assert gqa.paged_decode_call(INTERNLM2, [1000] * 8) == (65_536_000,
                                                            32_833_536)


def test_paged_decode_step_is_one_call_in_each_layer():
    # the step of the internlm2 case above: 24 layers of one call each
    assert gqa.paged_decode_step(INTERNLM2, [1000] * 8) == (
        24 * 65_536_000, 24 * 32_833_536)


def test_paged_decode_is_memory_bound_on_v5e():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    f, b = gqa.paged_decode_call(INTERNLM2, [1000] * 8)
    t, bound = flops.least_seconds(f, b, peak)
    assert bound == "memory" and t == b / 819e9


def test_decode_step_smollm():
    # per layer: 960 * (2*960 + 2*320) + 3 * 960 * 2560 = 9,830,400
    # stack 32 layers = 314,572,800; head 49152 * 960 = 47,185,920
    # one slot of 1 token: 2 * 361,758,720 + 32 * 4 * 15 * 64 * 1
    assert gqa.decode_step(SMOLLM, [1]) == 2 * 361_758_720 + 32 * 3840
