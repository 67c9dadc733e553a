"""Decode loop: drafts of the prediction module that stood over drafts the
verifying steps judged, over the decode steps of the timed window. The step
program's own count (its accept rule runs inside the executable and adds
the drafts fed to live rows and those that were the model's own token into
its counter fetch; the decode loop adds them into the engine's counters
``program_mtp_drafted`` / ``program_mtp_accepted``). Under seeded weights
the module knows nothing of the model and this reads near 0; at a
deployment's acceptance a, a step yields 1 + a tokens a row for the same
work. None where the program keeps no such counters."""


def read(ctx):
    before, after = ctx["window_counters"]
    drafted, stood = "program_mtp_drafted", "program_mtp_accepted"
    if drafted not in after:
        return None
    judged = after[drafted] - before.get(drafted, 0)
    if judged <= 0:
        return None
    return 100.0 * (after[stood] - before.get(stood, 0)) / judged
