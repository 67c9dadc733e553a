"""XLA-lowered ops: the held experts of a layer that a decode step's picks
reach, of all the layer's experts, over the decode steps of the timed
window and the expert layers (the prediction module's with the main
model's). Each ``routed_experts`` op leaves the tokens each held expert took;
the step program counts the experts that took any, adds them over its
layers into its counter fetch, and the decode loop into the engine's counter
``program_moe_experts_touched``; the steps are the engine's quanta less its
chunk runs. It checks the expectation that
``ops_count_glm_lite.decode_step`` reckons a step's expert bytes with (32
rows x 2 lanes x 4 picks over 64: 98.4%). None where the program keeps no
such counter."""


def read(ctx):
    before, after = ctx["window_counters"]
    name = "program_moe_experts_touched"
    # ``decode_steps`` counts every quantum, chunk runs too, and only a
    # step counts its experts
    steps = (after.get("decode_steps", 0) - before.get("decode_steps", 0)
             - after.get("prefill_chunks", 0)
             + before.get("prefill_chunks", 0))
    if name not in after or steps <= 0:
        return None
    config = ctx["run"].config
    first, count = config["layers_held"]
    layers = sum(l >= config["first_k_dense_replace"]
                 for l in range(first, first + count)) \
        + config.get("num_nextn_predict_layers", 0)
    if layers <= 0:
        return None
    touched = after[name] - before.get(name, 0)
    return 100.0 * touched / (steps * layers * config["n_routed_experts"])
