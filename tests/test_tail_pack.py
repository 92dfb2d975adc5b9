"""Tail pack: strings kernel set, randomized low-rank factorizations,
color/geometry vision transforms, executor statistics. Parity targets:
`paddle/phi/kernels/strings/`, paddle.linalg.svd_lowrank/pca_lowrank,
`python/paddle/vision/transforms/transforms.py`,
`new_executor/executor_statistics.cc`."""
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.vision import transforms as T

rng = np.random.RandomState(0)


def test_strings_lower_upper_unicode():
    s = paddle.strings.StringTensor([["Hello", "WORLD"], ["ÄÖü", "mIxEd"]])
    assert paddle.strings.lower(s).tolist() == [["hello", "world"],
                                                ["äöü", "mixed"]]
    assert paddle.strings.upper(s).tolist()[1] == ["ÄÖÜ", "MIXED"]
    # utf8 fast path only touches ascii code points
    lo = paddle.strings.lower(s, use_utf8_encoding=True)
    assert lo.tolist()[0] == ["hello", "world"]
    assert lo.tolist()[1] == ["ÄÖü", "mixed"]  # non-ascii untouched
    e = paddle.strings.empty([3])
    assert e.tolist() == ["", "", ""]
    assert e.shape == [3]


def test_svd_lowrank_reconstructs_lowrank_matrix():
    # the projection draws from the global key: unseeded, the result (and
    # the 1e-3 below, at q = rank with no oversampling) went with whatever
    # tests the worker had run before this one (seeds 0-11 read 3e-6 to 5e-4)
    paddle.seed(1)
    A = (rng.randn(32, 4) @ rng.randn(4, 24)).astype(np.float32)
    U, S, V = paddle.linalg.svd_lowrank(paddle.to_tensor(A), q=4)
    rec = (np.asarray(U._data) * np.asarray(S._data)) @ np.asarray(V._data).T
    assert np.abs(rec - A).max() < 1e-3
    # singular values match exact svd
    s_exact = np.linalg.svd(A, compute_uv=False)[:4]
    np.testing.assert_allclose(np.asarray(S._data), s_exact, rtol=1e-3)


def test_pca_lowrank_centers():
    A = (rng.randn(50, 3) @ rng.randn(3, 10) + 5.0).astype(np.float32)
    U, S, V = paddle.linalg.pca_lowrank(paddle.to_tensor(A), q=3)
    # 3 principal components capture everything (data is rank-3 + mean)
    centered = A - A.mean(0)
    energy = (np.asarray(S._data) ** 2).sum() / (centered ** 2).sum()
    assert energy > 0.999


def test_color_transforms_preserve_shape_and_range():
    img = (rng.rand(12, 12, 3) * 255).astype(np.uint8)
    for t in (T.ColorJitter(0.3, 0.3, 0.3, 0.1), T.SaturationTransform(0.5),
              T.HueTransform(0.3)):
        out = np.asarray(t(img))
        assert out.shape == (12, 12, 3)
        assert out.min() >= 0 and out.max() <= 255
    g = np.asarray(T.Grayscale(1)(img))
    assert g.shape == (12, 12, 1)
    g3 = np.asarray(T.Grayscale(3)(img))
    assert np.ptp(g3, axis=-1).max() == 0  # all channels equal


def test_hue_identity_at_zero():
    img = (rng.rand(8, 8, 3) * 255).astype(np.uint8)
    out = T.adjust_hue(img, 0.0)
    np.testing.assert_allclose(np.asarray(out).astype(np.int32),
                               img.astype(np.int32), atol=2)


def test_geometry_transforms():
    img = (rng.rand(16, 16, 3) * 255).astype(np.uint8)
    rot = np.asarray(T.RandomRotation((90, 90))(img))
    assert rot.shape == img.shape
    # 90-degree rotation keeps total mass approximately (borders clipped)
    er = T.RandomErasing(prob=1.0, value=0)(img.transpose(2, 0, 1))
    assert (np.asarray(er) == 0).any()
    pe = np.asarray(T.RandomPerspective(prob=1.0)(img))
    assert pe.shape == img.shape


def test_executor_statistics():
    ex = paddle.static.Executor()
    x = paddle.static.data("xs", [4], "float32")
    y = (x * 3.0).sum()
    ex.run(feed={"xs": np.ones(4, np.float32)}, fetch_list=[y])
    ex.run(feed={"xs": np.zeros(4, np.float32)}, fetch_list=[y])
    stats = ex.statistics()
    assert stats["runs"] == 2
    assert stats["compiles"] == 1  # second run hit the program cache
    assert stats["op_counts"].get("multiply", 0) >= 2
    import tempfile, os, json
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "stats.json")
        paddle.static.executor_statistics(ex, path)
        assert json.load(open(path))["runs"] == 2


def test_lookahead_and_model_average():
    """incubate.LookAhead / ModelAverage (reference incubate/optimizer/)."""
    paddle.seed(0)
    net = paddle.nn.Linear(4, 2)
    inner = paddle.optimizer.SGD(0.1, parameters=net.parameters())
    la = paddle.incubate.LookAhead(inner, alpha=0.5, k=2)
    ma = paddle.incubate.ModelAverage(0.2, parameters=net.parameters(),
                                      min_average_window=2)
    x = paddle.to_tensor(rng.randn(8, 4).astype(np.float32))
    y = paddle.to_tensor(rng.randn(8, 2).astype(np.float32))
    losses = []
    for _ in range(8):
        loss = ((net(x) - y) ** 2).mean()
        loss.backward()
        la.step(); la.clear_grad(); ma.step()
        losses.append(float(np.asarray(loss._data)))
    assert losses[-1] < losses[0]
    w_train = np.asarray(net.weight._data).copy()
    with ma.apply():
        assert not np.allclose(np.asarray(net.weight._data), w_train)
    np.testing.assert_allclose(np.asarray(net.weight._data), w_train)
    # double apply guarded; state roundtrip
    ma.apply(); ma.apply(); ma.restore()
    np.testing.assert_allclose(np.asarray(net.weight._data), w_train)
    sd = la.state_dict()
    la.set_state_dict(sd)
    ops, params_grads = la.minimize(((net(x) - y) ** 2).mean())
    assert ops == [] and len(params_grads) > 0
    # reference contract: minimize does NOT clear grads
    assert all(g is not None for _, g in params_grads)
    assert net.weight.grad is not None
    la.clear_grad()


def test_hub_local_and_version():
    """paddle.hub local-source protocol + version metadata
    (reference python/paddle/hub.py, generated version module)."""
    import os
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        with open(os.path.join(d, "hubconf.py"), "w") as f:
            f.write("dependencies = ['numpy']\n\n"
                    "def entry(n=4):\n"
                    "    '''entry doc.'''\n"
                    "    import paddle_tpu as paddle\n"
                    "    return paddle.nn.Linear(n, 2)\n")
        assert paddle.hub.list(d) == ["entry"]
        assert "entry doc" in paddle.hub.help(d, "entry")
        m = paddle.hub.load(d, "entry", n=6)
        assert list(m.weight.shape) == [6, 2]
    import pytest
    with pytest.raises(NotImplementedError):
        paddle.hub.list("repo", source="github")
    assert paddle.version.cuda() is False
    assert paddle.version.full_version == paddle.__version__
