"""Model-zoo tests — the analog of the reference's book tests
(``tests/book/``: build model, train a few steps, assert loss decreases)."""

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import models


def _train(spec, batch_size=8, steps=6, lr=0.01, opt=None):
    # deterministic init + dropout: the executor seeds the scope RNG from
    # the FIRST program it runs (the startup program), so seed both
    fluid.default_main_program().random_seed = 90125
    fluid.default_startup_program().random_seed = 90125
    opt = opt or fluid.optimizer.SGD(learning_rate=lr)
    opt.minimize(spec.loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(7)
    batch = spec.sample_batch(batch_size, rng)  # fixed batch: overfit check
    losses = []
    for _ in range(steps):
        loss_val, = exe.run(feed=batch, fetch_list=[spec.loss])
        losses.append(float(loss_val))
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses
    return losses


def test_mnist_mlp_trains():
    spec = models.mnist.mlp(hidden_sizes=(32,))
    losses = _train(spec, lr=0.1)
    assert losses[-1] < losses[0] * 0.95


def test_mnist_cnn_trains():
    spec = models.mnist.cnn()
    _train(spec, batch_size=4, lr=0.05)


def test_resnet_cifar_trains():
    spec = models.resnet.resnet_cifar10(depth=8)
    _train(spec, batch_size=4, steps=4, lr=0.05)


def test_resnet50_builds():
    spec = models.resnet.resnet_imagenet(depth=50, class_num=100,
                                         image_shape=(3, 64, 64))
    assert spec.flops_per_example and spec.flops_per_example > 0
    n_ops = len(fluid.default_main_program().global_block().ops)
    assert n_ops > 100


def test_vgg_trains():
    spec = models.vgg.vgg16(image_shape=(3, 32, 32))
    _train(spec, batch_size=4, steps=4, lr=0.01)


def test_se_resnext_builds_and_steps():
    spec = models.se_resnext.se_resnext50(image_shape=(3, 64, 64),
                                          class_num=10)
    _train(spec, batch_size=2, steps=3, lr=0.01)


def test_stacked_lstm_trains():
    spec = models.stacked_lstm.stacked_lstm_net(
        dict_size=100, emb_dim=16, hid_dim=16, stacked_num=2, seq_len=12)
    _train(spec, batch_size=4, steps=5, lr=0.05)


def test_transformer_trains():
    spec = models.transformer.transformer_base(
        src_vocab=64, trg_vocab=64, seq_len=16, d_model=32, d_ff=64,
        n_head=2, n_layer=2, dropout_rate=0.0)
    losses = _train(spec, batch_size=4, steps=6,
                    opt=fluid.optimizer.Adam(learning_rate=3e-3))
    assert losses[-1] < losses[0]


def test_bert_trains():
    spec = models.bert.bert_base(vocab_size=64, seq_len=16, d_model=32,
                                 d_ff=64, n_head=2, n_layer=2,
                                 dropout_rate=0.0)
    _train(spec, batch_size=4, steps=5,
           opt=fluid.optimizer.Adam(learning_rate=3e-3))


def test_deepfm_trains():
    spec = models.deepfm.deepfm(sparse_feature_dim=1000, num_fields=6,
                                embedding_size=4, dense_dim=3,
                                hidden_sizes=(16, 16))
    _train(spec, batch_size=8, steps=5,
           opt=fluid.optimizer.Adam(learning_rate=1e-2))


def test_baseline_builds_each_shape_small_and_refuses_an_unknown_name():
    import pytest

    def build(name, **kw):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            spec, batch = models.baseline(name, **kw)
        first = next(iter(spec.feeds.values()))
        return spec, batch, first.shape

    for name, batch, shape in [("transformer", 4, (64,)),
                               ("bert", 4, (32,)),
                               ("resnet50", 2, (3, 64, 64)),
                               ("deepfm", 16, (26,))]:
        spec, got, first = build(name, small=True)
        assert (got, first) == (batch, shape), name
        feed = spec.sample_batch(got)
        assert set(feed) == set(spec.feed_names()), name
        assert all(v.shape[0] == batch for v in feed.values()), name
    assert build("transformer", small=True, seq_len=128)[1:] == (4, (128,))
    # on the chip the transformer's batch holds 32,768 tokens a step
    assert build("transformer", seq_len=2048)[1:] == (16, (2048,))
    with pytest.raises(ValueError, match="unknown BASELINE shape"):
        build("vgg")
    with pytest.raises(ValueError, match="seq_len"):
        build("bert", seq_len=64)


def test_word2vec_trains():
    spec = models.word2vec.ngram_lm(dict_size=50, emb_dim=8, hidden_size=16)
    _train(spec, batch_size=8, steps=5, lr=0.1)


def test_machine_translation_trains():
    spec = models.machine_translation.seq2seq_attention(
        src_vocab=40, trg_vocab=40, seq_len=10, emb_dim=16, hid_dim=16)
    _train(spec, batch_size=4, steps=5,
           opt=fluid.optimizer.Adam(learning_rate=3e-3))


def test_ocr_ctc_trains():
    spec = models.ocr_ctc.crnn_ctc(num_classes=12, image_shape=(1, 16, 48),
                                   max_label_len=6, hid_dim=16)
    _train(spec, batch_size=4, steps=5,
           opt=fluid.optimizer.Adam(learning_rate=3e-3))


def test_ssd_lite_trains_and_detects():
    spec = models.ssd.ssd_lite()
    _train(spec, batch_size=2, steps=4,
           opt=fluid.optimizer.Adam(learning_rate=2e-3))
    # inference outputs exist with the fixed-shape contract
    dets = spec.fetches["detections"]
    cnt = spec.fetches["det_count"]
    exe = fluid.Executor(fluid.CPUPlace())
    batch = spec.sample_batch(2, np.random.RandomState(1))
    d, c = exe.run(feed=batch, fetch_list=[dets, cnt])
    assert d.shape[1:] == (10, 6) and (c >= 0).all()


def test_srl_crf_trains_and_decodes():
    spec = models.label_semantic_roles.srl_crf()
    _train(spec, batch_size=4, steps=5,
           opt=fluid.optimizer.Adam(learning_rate=5e-3))
    exe = fluid.Executor(fluid.CPUPlace())
    batch = spec.sample_batch(4, np.random.RandomState(2))
    path, = exe.run(feed=batch, fetch_list=[spec.fetches["decoded"]])
    assert path.shape == (4, 16)
    assert (path >= 0).all() and (path < 20).all()


def test_book_models_train():
    for builder, kwargs, bs in (
            (models.books.fit_a_line, {}, 8),
            (models.books.understand_sentiment, {"seq_len": 12,
                                                 "stacked_num": 2}, 4),
            (models.books.recommender_system, {}, 8)):
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 90125
        scope = fluid.Scope()
        with fluid.program_guard(main, startup), fluid.scope_guard(scope):
            spec = builder(**kwargs)
            fluid.optimizer.Adam(learning_rate=5e-3).minimize(spec.loss)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            batch = spec.sample_batch(bs, np.random.RandomState(3))
            losses = [float(exe.run(main, feed=batch,
                                    fetch_list=[spec.loss])[0])
                      for _ in range(6)]
        assert np.isfinite(losses).all(), (builder.__name__, losses)
        assert losses[-1] < losses[0], (builder.__name__, losses)


# ---------------------------------------------------------------------------
# Held-out quality bars (VERDICT r3 ask #5) — the analog of the reference
# book tests' quality asserts (``tests/book/test_recognize_digits.py``
# trains to an error bar, not just "loss decreased"): train on structured
# synthetic data, evaluate on HELD-OUT samples via clone(for_test=True),
# and assert the eval loss clears a chance-level bar.
# ---------------------------------------------------------------------------


def _quality_run(build, make_batch, train_steps, bar, lr=3e-3, bs=16):
    fluid.unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1234
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        spec = build()
        test_prog = main.clone(for_test=True)
        fluid.optimizer.Adam(learning_rate=lr).minimize(spec.loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(0)
        for _ in range(train_steps):
            exe.run(main, feed=make_batch(rng, bs),
                    fetch_list=[spec.loss])
        # held-out: fresh samples from the same task distribution
        held_rng = np.random.RandomState(999)
        evs = [float(exe.run(test_prog, feed=make_batch(held_rng, bs),
                             fetch_list=[spec.loss])[0])
               for _ in range(4)]
    ev = float(np.mean(evs))
    assert np.isfinite(ev) and ev < bar, (evs, "bar", bar)
    return ev


def test_transformer_heldout_quality():
    """Reverse-copy translation: held-out eval loss must beat chance
    (ln 32 = 3.47) by >2x after a short training run."""
    V, T = 32, 12

    def build():
        return models.transformer.transformer_base(
            src_vocab=V, trg_vocab=V, seq_len=T, d_model=32, d_ff=64,
            n_head=2, n_layer=2, dropout_rate=0.0, label_smooth_eps=0.0)

    def make_batch(rng, bs):
        src = rng.randint(2, V, (bs, T)).astype("int64")
        lbl = src[:, ::-1].copy()
        trg = np.concatenate([np.ones((bs, 1), "int64"), lbl[:, :-1]],
                             axis=1)
        return {"src_ids": src, "trg_ids": trg, "lbl_ids": lbl,
                "src_len": np.full((bs,), T, "int64"),
                "trg_len": np.full((bs,), T, "int64")}

    _quality_run(build, make_batch, train_steps=400, bar=np.log(32) / 2,
                 lr=5e-3)


def test_resnet_cifar_heldout_quality():
    """4-way pattern classification: held-out eval loss far below chance
    (ln 4 = 1.39)."""
    def build():
        return models.resnet.resnet_cifar10(depth=8, class_num=4)

    def make_batch(rng, bs):
        label = rng.randint(0, 4, (bs, 1)).astype("int64")
        img = rng.randn(bs, 3, 32, 32).astype("float32") * 0.25
        # class-dependent quadrant brightness pattern
        for i, c in enumerate(label[:, 0]):
            img[i, :, (c // 2) * 16:(c // 2) * 16 + 16,
                (c % 2) * 16:(c % 2) * 16 + 16] += 1.0
        return {"img": img, "label": label}

    _quality_run(build, make_batch, train_steps=60, bar=np.log(4) / 2,
                 lr=2e-3, bs=16)


def test_word2vec_heldout_quality():
    """Deterministic n-gram rule (next = f(first context word)): held-out
    loss far below chance (ln 40 = 3.69)."""
    V, W = 40, 4

    def build():
        return models.word2vec.ngram_lm(dict_size=V, emb_dim=16,
                                        hidden_size=32, window=W)

    def make_batch(rng, bs):
        ctx = rng.randint(0, V, (bs, W)).astype("int64")
        # lookup rule: next word determined by the first context word
        nxt = ((ctx[:, 0] + 1) % V).astype("int64")[:, None]
        feed = {"w%d" % i: ctx[:, i:i + 1] for i in range(W)}
        feed["next_word"] = nxt
        return feed

    _quality_run(build, make_batch, train_steps=200, bar=np.log(40) / 2,
                 lr=5e-3, bs=32)
