"""Metric ops: accuracy / auc / precision-recall.

Reference: ``paddle/fluid/operators/metrics/`` (accuracy_op, auc_op).
AUC keeps histogram state in persistable vars updated functionally each step
(the executor writes them back), matching the reference's stateful AUC op.
"""

import jax
import jax.numpy as jnp

from ..op_registry import register, get, put


@register("accuracy")
def _accuracy(env, op):
    # int32 on purpose: int64 is unavailable without x64 mode, and an
    # explicit astype(int64) emits a truncation UserWarning on every
    # trace while silently computing in int32 anyway
    pred_idx = get(env, op.input("Indices")).astype(jnp.int32)  # [N, k] ids
    label = get(env, op.input("Label")).astype(jnp.int32)
    if label.ndim == 1:
        label = label[:, None]
    correct = jnp.any(pred_idx == label, axis=1)
    num_correct = jnp.sum(correct.astype(jnp.float32))
    total = pred_idx.shape[0]
    put(env, op.output("Accuracy"), (num_correct / total).reshape(()))
    put(env, op.output("Correct"), num_correct.astype(jnp.int32).reshape((1,)))
    put(env, op.output("Total"), jnp.asarray([total], dtype=jnp.int32))


@register("auc")
def _auc(env, op):
    """Streaming AUC over threshold buckets (ref ``auc_op.cc``)."""
    preds = get(env, op.input("Predict"))  # [N, 2] binary probs
    labels = get(env, op.input("Label")).astype(jnp.int32).reshape(-1)
    stat_pos = get(env, op.input("StatPos"))
    stat_neg = get(env, op.input("StatNeg"))
    num_thresholds = op.attr("num_thresholds", 4095)
    pos_prob = preds[:, -1]
    bucket = jnp.clip((pos_prob * num_thresholds).astype(jnp.int32),
                      0, num_thresholds)
    pos_hist = jnp.zeros_like(stat_pos).at[bucket].add(labels.astype(stat_pos.dtype))
    neg_hist = jnp.zeros_like(stat_neg).at[bucket].add((1 - labels).astype(stat_neg.dtype))
    new_pos = stat_pos + pos_hist
    new_neg = stat_neg + neg_hist
    # trapezoid over descending thresholds
    tp = jnp.cumsum(new_pos[::-1])
    fp = jnp.cumsum(new_neg[::-1])
    tot_pos = tp[-1]
    tot_neg = fp[-1]
    tp_prev = jnp.concatenate([jnp.zeros((1,), tp.dtype), tp[:-1]])
    fp_prev = jnp.concatenate([jnp.zeros((1,), fp.dtype), fp[:-1]])
    area = jnp.sum((fp - fp_prev) * (tp + tp_prev) / 2.0)
    auc = jnp.where(tot_pos * tot_neg > 0, area / (tot_pos * tot_neg + 1e-12), 0.0)
    put(env, op.output("AUC"), auc.reshape(()))
    put(env, op.output("StatPosOut"), new_pos)
    put(env, op.output("StatNegOut"), new_neg)


@register("precision_recall")
def _precision_recall(env, op):
    pred_idx = get(env, op.input("Indices")).astype(jnp.int32).reshape(-1)
    label = get(env, op.input("Labels")).astype(jnp.int32).reshape(-1)
    cls_num = op.attr("class_number")
    onehot_p = jax.nn.one_hot(pred_idx, cls_num)
    onehot_l = jax.nn.one_hot(label, cls_num)
    tp = jnp.sum(onehot_p * onehot_l, axis=0)
    fp = jnp.sum(onehot_p * (1 - onehot_l), axis=0)
    fn = jnp.sum((1 - onehot_p) * onehot_l, axis=0)
    precision = tp / jnp.maximum(tp + fp, 1e-12)
    recall = tp / jnp.maximum(tp + fn, 1e-12)
    f1 = 2 * precision * recall / jnp.maximum(precision + recall, 1e-12)
    macro = jnp.stack([jnp.mean(precision), jnp.mean(recall), jnp.mean(f1)])
    tps, fps, fns = jnp.sum(tp), jnp.sum(fp), jnp.sum(fn)
    micro_p = tps / jnp.maximum(tps + fps, 1e-12)
    micro_r = tps / jnp.maximum(tps + fns, 1e-12)
    micro_f = 2 * micro_p * micro_r / jnp.maximum(micro_p + micro_r, 1e-12)
    micro = jnp.stack([micro_p, micro_r, micro_f])
    put(env, op.output("BatchMetrics"), jnp.concatenate([macro, micro]))
    put(env, op.output("AccumStatesInfo"), jnp.stack([tp, fp, fn], axis=1))
