"""The plain reference's blocked forward and its per-chip gradient
shares, and the verdict's use of them."""
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import graphgen  # noqa: E402
import reference  # noqa: E402
import verdict  # noqa: E402

OPT = {"lr": 0.005, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
       "weight_decay": 0.0}
DIMS = (16, 32, 32, 5)


def _case(seed=3):
    g = graphgen.make_graph(graphgen.seed_key(seed), n_nodes=600,
                            n_edges=4000, n_feats=DIMS[0], n_classes=5,
                            homophily=0.5, feature_noise=1.0,
                            train_share=0.6, val_share=0.2)
    params = jax.tree.map(np.asarray, graphgen.init_params(
        graphgen.seed_key(seed + 1), DIMS))
    return g, params


def test_blocked_forward_equals_whole(monkeypatch):
    """Blocks of 1,000 of the 8,600 edges, the last one padded: the same
    three steps up to float32 summation order."""
    g, params = _case()
    whole = reference.train3(params, g, OPT, precision="highest")
    monkeypatch.setattr(reference, "WHOLE_BYTES", 0)
    monkeypatch.setattr(reference, "BLOCK_BYTES", 1000 * 4 * max(DIMS[:-1]))
    assert reference.blocks_for(8600, max(DIMS[:-1])) == 1000
    blocked = reference.train3(params, g, OPT, precision="highest")
    np.testing.assert_allclose(blocked[0], whole[0], rtol=1e-6)
    for got, want in zip(jax.tree.leaves(blocked[1:]),
                         jax.tree.leaves(whole[1:])):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def test_configured_cells_run_whole():
    """arxiv's and Flickr's messages fit whole: their arithmetic is the
    one segment-sum it always was."""
    assert reference.blocks_for(2_501_829, 256) is None
    assert reference.blocks_for(989_006, 500) is None
    assert reference.blocks_for(26_112_748, 256) == 976_562


def test_chip_shares_add_up_to_the_gradient():
    g, params = _case()
    _, grad, _ = reference.train3(params, g, OPT, precision="highest")
    n = g["labels"].shape[0]
    one = reference.chip_grad_norms(params, g, np.zeros(n), 1, "highest")
    last = reference.chip_grad_norms(params, g, np.full(n, 2), 3, "highest")
    for layer, o, la in zip(grad, one, last):
        norm = np.linalg.norm(layer["w"])
        np.testing.assert_allclose(o, [norm], rtol=1e-5)
        np.testing.assert_allclose(la, [0, 0, norm], rtol=1e-5)


def test_scale_follows_the_chip_shares():
    """A program whose weight gradient carries the expected RP scale for
    chip shares that sum to a quarter of the squared norm reads 0."""
    g, params = _case()
    _, grad, p3 = reference.train3(params, g, OPT, precision="highest")
    rp, quarter = 8, lambda w: [np.linalg.norm(w) / 4] * 4
    ref = {"losses": [1.0] * 3, "grad": grad, "params3": p3,
           "chip_grad_norms": [quarter(layer["w"]) for layer in grad]}
    scaled = [{"b": layer["b"],
               "w": layer["w"] * verdict.rp_scale(layer["w"].shape[0], rp,
                                                  0.25)}
              for layer in grad]
    prog = dict(ref, grad=scaled)
    assert verdict.readings(prog, ref, params, rp)["grad_scale_gap"] < 1e-6
    single = dict(ref)
    del single["chip_grad_norms"]
    assert verdict.readings(prog, single, params, rp)["grad_scale_gap"] > 0.3
