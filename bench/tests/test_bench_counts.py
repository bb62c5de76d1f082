"""FLOP and byte counts against hand-worked numbers at arxiv shapes."""
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import counts  # noqa: E402

N = 169_343


def test_model_flops_arxiv_sage():
    # layer 0: (N, 256) @ (256, 256), forward and dW only:
    #   2 * 169,343 * 256 * 256 = 22,196,125,696 FLOPs, twice
    # layer 1: (N, 512) @ (512, 256), forward, dW, dX:
    #   2 * 169,343 * 512 * 256 = 44,392,251,392, three times
    # layer 2: (N, 512) @ (512, 40):
    #   2 * 169,343 * 512 * 40 = 6,936,289,280, three times
    want = 2 * 22_196_125_696 + 3 * 44_392_251_392 + 3 * 6_936_289_280
    assert want == 198_377_873_408
    assert counts.model_flops(N, (128, 256, 256, 40)) == want


def test_quant_kernel_bytes_arxiv_layer0():
    # layer 0 after RP 8: 169,343 rows of 32 -> 21,168 blocks of 256
    # in:  21,168 * 256 * 4 B = 21,676,032
    # out: 21,168 * 16 words * 4 B = 1,354,752 packed
    #      + 2 * 21,168 * 4 B = 169,344 zero and range
    ops = [("u32", (1, 1)), ("f32", (21_168, 256))]
    res = [("u32", (21_168, 16)), ("f32", (21_168, 1)), ("f32", (21_168, 1))]
    assert counts.kernel_cost("quant", ops, res) == (0, 23_200_128)
    assert counts.kernel_cost("dequant", res, [("f32", (21_168, 256))]) == (
        0, 23_200_128)


def test_fused_kernel_cost_arxiv_layer1():
    # forward: x (169,472 padded rows, 512) @ w (512, 256)
    m, d, n = 169_472, 512, 256
    nb = m * d // 128
    ops = [("u32", (1, 1)), ("f32", (m, d)), ("f32", (d, n))]
    res = [("f32", (m, n)), ("u32", (nb, 8)), ("f32", (nb, 1)),
           ("f32", (nb, 1))]
    flops, nbytes = counts.kernel_cost("fused_fwd", ops, res)
    assert flops == 2 * 169_472 * 512 * 256 == 44_426_067_968
    assert nbytes == (4 * m * d + 4 * d * n + 4 * m * n + 4 * nb * 8
                      + 8 * nb)
    # backward: codes and g in, one (512, 256) f32 gradient out however
    # many partials the kernel writes
    ops = [("u32", (nb, 8)), ("f32", (nb, 1)), ("f32", (nb, 1)),
           ("f32", (m, n))]
    flops, nbytes = counts.kernel_cost("fused_bwd", ops,
                                       [("f32", (662, d, n))])
    assert flops == 44_426_067_968
    assert nbytes == 4 * nb * 8 + 8 * nb + 4 * m * n + 4 * d * n
