"""Shared model-zoo plumbing: ModelSpec + synthetic batch sampling, and
the BASELINE.json shapes by name (``baseline``)."""

import numpy as np

__all__ = ["ModelSpec", "FeedSpec", "baseline"]


class FeedSpec:
    """Shape/dtype/range of one feed tensor (batch dim excluded)."""

    def __init__(self, shape, dtype="float32", low=None, high=None):
        self.shape = tuple(shape)
        self.dtype = dtype
        self.low = low
        self.high = high

    def sample(self, batch_size, rng):
        shape = (batch_size,) + self.shape
        if np.issubdtype(np.dtype(self.dtype), np.integer):
            low = 0 if self.low is None else self.low
            high = 2 if self.high is None else self.high
            return rng.randint(low, high, size=shape).astype(self.dtype)
        low = -1.0 if self.low is None else self.low
        high = 1.0 if self.high is None else self.high
        return rng.uniform(low, high, size=shape).astype(self.dtype)


class ModelSpec:
    """What a model builder returns.

    Attributes:
      loss: scalar loss Variable (train target).
      feeds: ordered dict name -> FeedSpec (synthetic-data recipe).
      fetches: extra fetch Variables by name (e.g. accuracy).
      flops_per_example: analytic fwd+bwd FLOPs per example (for MFU calc);
        None if not computed.
      tokens_per_example: for sequence models, tokens per example.
      sequence_feeds: feed names whose dim 1 is the sequence axis —
        callers pass these to ``with_data_parallel(sequence_feeds=...)``
        for sequence-parallel sharding (explicit beats the executor's
        opt-in heuristic). None (the default for specs not yet
        annotated) keeps with_data_parallel's own default behavior
        rather than silently pinning feeds to dp-only.
    """

    def __init__(self, loss, feeds, fetches=None, flops_per_example=None,
                 tokens_per_example=None, extras=None,
                 sequence_feeds=None):
        self.loss = loss
        self.feeds = feeds
        self.fetches = dict(fetches or {})
        self.flops_per_example = flops_per_example
        self.tokens_per_example = tokens_per_example
        self.sequence_feeds = (list(sequence_feeds)
                               if sequence_feeds is not None else None)
        # named internal vars (e.g. pipeline cut points, block outputs)
        self.extras = dict(extras or {})

    def feed_names(self):
        return list(self.feeds.keys())

    def sample_batch(self, batch_size, rng=None):
        rng = rng or np.random.RandomState(0)
        return {name: fs.sample(batch_size, rng)
                for name, fs in self.feeds.items()}


def baseline(name, small=False, seq_len=None):
    """``(spec, batch)`` of one BASELINE.json shape, built into the current
    default program: ``transformer`` (base NMT; ``seq_len`` 256 unless
    given, the batch holding 32,768 tokens a step), ``bert`` (base
    pretrain, b128 s128), ``resnet50`` (ImageNet, b128) or ``deepfm``
    (100k-row fused table, b32768). ``small`` builds the widths the quick
    tests use."""
    from . import bert, deepfm, resnet, transformer

    if seq_len is not None and name != "transformer":
        raise ValueError("seq_len applies to 'transformer', not %r" % name)
    if name == "transformer":
        seq_len = seq_len or (64 if small else 256)
        spec = transformer.transformer_base(seq_len=seq_len,
                                            dropout_rate=0.1)
        return spec, 4 if small else max(1, 128 * 256 // seq_len)
    if name == "bert":
        if small:
            return bert.bert_base(vocab_size=1000, seq_len=32, d_model=128,
                                  d_ff=256, n_layer=2), 4
        return bert.bert_base(seq_len=128), 128
    if name == "resnet50":
        if small:
            return resnet.resnet_imagenet(depth=50, class_num=10,
                                          image_shape=(3, 64, 64)), 2
        return resnet.resnet_imagenet(depth=50), 128
    if name == "deepfm":
        if small:
            return deepfm.deepfm(sparse_feature_dim=1000,
                                 hidden_sizes=(64, 64)), 16
        return deepfm.deepfm(), 32768
    raise ValueError("unknown BASELINE shape %r; have transformer, bert, "
                     "resnet50, deepfm" % (name,))
