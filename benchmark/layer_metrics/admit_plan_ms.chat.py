"""``admit_plan_ms`` in a cell judged on latency: the same reading
(``admit_plan_ms.py``) under a name of its own, because there it moves
``token_ms_mean`` and not ``serve_tokens_per_s``."""

import os

from benchmark import harness

read = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "admit_plan_ms.py")).read
