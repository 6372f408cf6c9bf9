"""Operation and byte counts against hand-counted small shapes, and the
qwen2 architecture's counts for the cell's own configuration pinned."""
import pytest

from bench import arch, common, counts, peaks

QWEN2 = arch.by_name("Qwen2ForCausalLM")
PEAKS = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
# d=8, 2 heads of 4 (q=8), 1 kv head (k=4), f=16, V=32, 2 layers.
Z = dict(d=8, h=2, hkv=1, hd=4, f=16, V=32, L=2)


def test_gemm_counts():
    assert counts.gemm(2, 3, 4) == (48, 2 * 3 + 3 * 4 + 2 * 4 * 2)
    assert counts.gemm(2, 3, 4, a_bytes=2, b_bytes=2, out_bytes=4) == (
        48, 12 + 24 + 32)


@pytest.mark.parametrize("ops,nbytes,want", [
    (1000.0, 10.0, 10.0),      # compute-bound: 1000 / 100
    (100.0, 50.0, 5.0),        # memory-bound: 50 / 10
])
def test_least_time_takes_the_larger_bound(ops, nbytes, want):
    assert counts.least_time(ops, nbytes, PEAKS) == pytest.approx(want)


def test_projection_params():
    # wq 8x8, wk 8x4, wv 8x4, wo 8x8, gate/up 8x16, down 16x8
    assert QWEN2.layer_matmul_params(Z) == 64 + 32 + 32 + 64 + 3 * 128


def test_causal_pairs_count_the_lower_triangle():
    assert counts.causal_pairs(1) == 1
    assert counts.causal_pairs(4) == 10
    # about half of the full square at length
    assert counts.causal_pairs(1024) / 1024 ** 2 == pytest.approx(0.5, 1e-3)


def test_train_flops_per_token():
    seq = 4
    dense = 2 * (2 * 576 + 8 * 32)            # layers + head, forward
    attn = 2 * 4 * 2 * 4 * 10 / seq           # 4 h hd pairs / seq, 2 layers
    assert QWEN2.train_flops_per_token(Z, seq) == pytest.approx(
        3 * (dense + attn))


def test_train_gemm_least_time_counts_three_products_per_projection():
    tokens = 4
    want = 0.0
    for k, n in QWEN2.projections(Z):
        for m_, k_, n_ in ((tokens, k, n), (tokens, n, k), (k, tokens, n)):
            want += counts.least_time(2 * m_ * k_ * n_,
                                      m_ * k_ + k_ * n_ + 2 * m_ * n_, PEAKS)
    assert QWEN2.train_gemm_least_time(Z, tokens, PEAKS) == pytest.approx(
        2 * want)


def test_train_attention_backward_is_twice_the_forward():
    big = {"bf16_flops": 1.0, "hbm_bytes_per_s": 1e30}   # compute-bound
    fwd = 4 * 1 * 2 * 4 * counts.causal_pairs(4)
    assert QWEN2.train_attn_least_time(Z, 1, 4, big) == pytest.approx(
        2 * 3 * fwd)


# The counts of the cell's configuration (qwen2-1.5b, 16 layers) on one
# TPU v5 lite, as the readers of mfu.train, gemm_roofline.train and
# attn_roofline.train take them: the train.4k mix's 1024-token rows, 4 of
# them a step. Pinned exactly, so that moving the counts changes no number
# those metrics read.
@pytest.mark.parametrize("count,args,want", [
    ("train_flops_per_token", (1024,), 6043484160.0),
    ("train_gemm_least_time", (4096, "peaks"), 0.09342391632560593),
    ("train_attn_least_time", (4, 1024, "peaks"), 0.0031425343675126906),
])
def test_qwen2_cell_counts_are_pinned(count, args, want):
    m = common.load_json(common.HERE / "configs" / "qwen2-1.5b.fused.json")
    a = arch.load(m)
    pk = peaks.peaks_for("TPU v5 lite")
    got = getattr(a, count)(a.dims(m), *(pk if x == "peaks" else x
                                         for x in args))
    assert got == want
