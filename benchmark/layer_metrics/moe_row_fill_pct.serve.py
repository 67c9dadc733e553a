"""XLA-lowered ops: the share of the rows the held experts' products ran
over that held a token, over the decode steps of the timed window and the
expert layers. Each ``routed_experts`` op counts the rows of its table that
hold a token and the rows its products ran over (each expert's padded up to
whole blocks); the step program adds them over its layers into its counter
fetch and the decode loop into the engine's counters
``program_moe_rows_held`` / ``program_moe_rows_run``. None where the
program keeps no such counters."""


def read(ctx):
    before, after = ctx["window_counters"]
    held, run = "program_moe_rows_held", "program_moe_rows_run"
    if run not in after:
        return None
    ran = after[run] - before.get(run, 0)
    if ran <= 0:
        return None
    return 100.0 * (after[held] - before.get(held, 0)) / ran
