"""Small versions of the benchmark's cells, for rehearsals on the CPU:
the same drivers, references and checks, at sizes a test can hold."""

import copy
import io
import json
import shutil
import tempfile
from contextlib import redirect_stdout

from benchkit import harness

TINY_GRANITE = dict(hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
                    intermediate_size=512, vocab_size=2048, num_hidden_layers=2)
# lm_logit_gap at this size, read on the CPU over seeds 1..12, 40 and
# 2**31 + 17 (4 requests of 24 tokens compared each): the program 0 to
# 0.0067, the float8 control 0.0290 to 0.0630; the limit lies between,
# nearer the control
TINY_LM_LIMIT = 0.02


def tiny(workload: str) -> dict:
    """The cell resolved from the real files, with sizes cut for a CPU."""
    r = copy.deepcopy(harness.resolve(harness.load_spec(), workload))
    driver = r["traffic"]["driver"]
    if driver == "lm_closed":
        r["config"].update(TINY_GRANITE)
        r["config"]["limits"]["lm_logit_gap"] = TINY_LM_LIMIT
        r["traffic"].update(clients=4, prompt_tokens=32, generated_tokens=24,
                            engine={"max_batch": 4, "max_wait_s": 0.5},
                            check_requests=4)
    elif driver == "campaign_closed":
        r["traffic"].update(spec=dict(n_train=6, n_qor_samples=4, pop_size=4,
                                      n_parents=2, n_generations=1),
                            warm_buckets=[4, 8])
    return r


def run(workload: str, *, seed: int = 2 ** 31 + 11, seconds: float = 2.0,
        trace: int = 0, resolved=None) -> dict:
    """One rehearsal run in this process; returns the parsed result line."""
    out = io.StringIO()
    workdir = tempfile.mkdtemp(prefix="bench-rehearsal-")
    try:
        with redirect_stdout(out):
            harness.main(["--workload", workload, "--seed", str(seed),
                          "--seconds", str(seconds), "--trace", str(trace)],
                         require_accelerator=False, compile_cache=False,
                         resolved=resolved or tiny(workload), workdir=workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return json.loads(out.getvalue().strip().splitlines()[-1])
