"""Closed-loop LM serving: ``clients`` callers, each sending one prompt,
waiting for its generated tokens and sending the next, through the
serving engine's LM backend (the jitted prefill and decode of the
program's Generator).

Traffic parameters: ``clients``, ``prompt_tokens``, ``generated_tokens``,
``tier``, ``engine`` (ServingEngine options) and ``check_requests`` (how
many finished requests the reference re-runs).  Callers stop sending at
the close of the window; what they sent is drained and counted.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import threading
import time

import numpy as np

from .. import lmcost
from ..refs import granite as ref
from ..stats import percentile, sample

_PROGRAM_KEYS = {  # configuration key -> the program's ModelConfig field
    "hidden_size": "d_model", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "num_hidden_layers": "n_layers",
    "rms_norm_eps": "rms_eps", "rope_theta": "rope_theta",
}


def program_config(c: dict):
    """The program's ModelConfig for configuration file ``c``: the named
    model with every size the file states."""
    from repro.configs import get_config

    cfg = dataclasses.replace(
        get_config(c["model"]),
        **{f: c[k] for k, f in _PROGRAM_KEYS.items()})
    if cfg.tie_embeddings or cfg.resolved_head_dim * cfg.n_heads != cfg.d_model:
        raise ValueError(f"{c['model']} is not the untied llama-style decoder "
                         "the reference implements")
    return cfg


def program_dtypes(c: dict, cfg) -> dict:
    """{path: dtype} of the program's parameter tree, which has to hold
    the configuration's weights leaf for leaf: the same paths and shapes.
    The program keeps its weights in its own storage type; the benchmark
    hands it the configuration's values in that type."""
    import jax

    from repro.models.common import ParamSpec
    from repro.models.transformer import param_specs

    leaves = jax.tree_util.tree_flatten_with_path(
        param_specs(cfg), is_leaf=lambda x: isinstance(x, ParamSpec))[0]
    got = {"/".join(str(k.key) for k in path): spec for path, spec in leaves}
    want = ref.weight_shapes(c)
    diff = sorted(p for p in set(got) | set(want)
                  if p not in got or p not in want
                  or tuple(got[p].shape) != tuple(want[p][0]))
    if diff:
        raise ValueError("the program's parameter tree differs from the "
                         f"configuration's weights at {diff}")
    return {p: spec.dtype for p, spec in got.items()}


class Driver:
    def __init__(self, run):
        self.run = run
        self.c = run.config
        self.t = run.traffic
        self.attempted = 0
        self.failed = 0
        self.records = []      # (client, k, t_send, t_done, n_tokens)
        self.served = {}       # (client, k) -> generated tokens
        self._lock = threading.Lock()

    # -- inputs ------------------------------------------------------------
    def prompt(self, client: int, k: int) -> np.ndarray:
        rng = np.random.default_rng([self.run.seed, client, k])
        return rng.integers(0, self.c["vocab_size"], self.t["prompt_tokens"],
                            dtype=np.int32)

    # -- set-up ------------------------------------------------------------
    def setup(self):
        from repro.accel.lm import LMAccelerator
        from repro.core.acl.library import default_library
        from repro.serving import FrontCatalog, ServingEngine

        cfg = program_config(self.c)
        self.accel = LMAccelerator(cfg, use_reduced=False)
        self.dtypes = program_dtypes(self.c, cfg)
        self.accel._params = self.program_weights(self.run.seed)
        lib = default_library()
        genome = self.accel.exact_genome(lib)
        catalog = FrontCatalog.from_front(self.accel.name, [genome.tolist()],
                                          [[-100.0, 1.0]])
        self.engine = ServingEngine(self.accel, lib, catalog=catalog,
                                    **self.t.get("engine", {}))
        # one group of the window's shape: compiles prefill and decode
        warm = [self.engine.submit(self.prompt(i, 1 << 30), tier=self.t["tier"],
                                   gen=self.t["generated_tokens"])
                for i in range(self.t["clients"])]
        for f in warm:
            f.result(timeout=900)
        self.base = self.engine.stats()

    def program_weights(self, seed: int):
        import jax

        return jax.block_until_ready(ref.make_weights(self.c, seed, self.dtypes))

    # -- the window --------------------------------------------------------
    def _client(self, i: int):
        k = 0
        while time.perf_counter() < self.t_end:
            t_send = time.perf_counter()
            with self._lock:
                self.attempted += 1
            try:
                res = self.engine.submit(
                    self.prompt(i, k), tier=self.t["tier"],
                    gen=self.t["generated_tokens"]).result(timeout=600)
                t_done = time.perf_counter()
                toks = np.asarray(res["tokens"], dtype=np.int64)
                with self._lock:
                    self.records.append((i, k, t_send, t_done, len(toks)))
                    self.served[(i, k)] = toks
            except Exception as exc:  # noqa: BLE001 - a failed request is counted
                self.run.log(f"request {i}/{k} failed: {exc!r}")
                with self._lock:
                    self.failed += 1
                    self.records.append((i, k, t_send, math.inf, 0))
            k += 1

    def _gc_timer(self, phase, info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None:
            self.gc_pauses.append((self._gc_t - self.t0,
                                   time.perf_counter() - self._gc_t,
                                   info.get("generation")))
            self._gc_t = None

    def window(self, t0: float, t_end: float):
        self.t0, self.t_end = t0, t_end
        self.gc_pauses, self._gc_t = [], None
        gc.callbacks.append(self._gc_timer)
        self.threads = [threading.Thread(target=self._client, args=(i,))
                        for i in range(self.t["clients"])]
        for th in self.threads:
            th.start()
        time.sleep(max(0.0, t_end - time.perf_counter()))

    def finish(self):
        for th in self.threads:
            th.join()
        gc.callbacks.remove(self._gc_timer)
        self.stats = self.engine.stats()
        lat = sorted(r[3] - r[2] for r in self.records)
        if lat:
            self.run.log(
                f"{len(lat)} requests in {self.stats['groups'] - self.base['groups']}"
                f" groups; latency min {lat[0]:.3f} s, median "
                f"{lat[len(lat) // 2]:.3f} s, max {lat[-1]:.3f} s")
            self._log_groups()

    def _log_groups(self):
        """Each group's requests, from the window's start: when the first
        was sent, when the last came back and how many there were; and
        the longest pauses of Python's collector in the window.  A group
        far slower than the others is named with what it overlapped."""
        groups = []
        for r in sorted(self.records, key=lambda r: r[3]):
            if groups and r[3] - groups[-1][1] < 0.02:
                g = groups[-1]
                groups[-1] = (min(g[0], r[2]), r[3], g[2] + 1)
            else:
                groups.append((r[2], r[3], 1))
        spans = [(a - self.t0, b - a, n) for a, b, n in groups]
        med = sorted(d for _, d, _ in spans)[len(spans) // 2]
        slow = [g for g in spans if g[1] > 1.25 * med]
        top = sorted(self.gc_pauses, key=lambda p: -p[1])[:3]
        self.run.log(f"groups (start s, seconds, requests): "
                     f"{[tuple(round(v, 3) for v in g) for g in spans]}; "
                     f"longest collector pauses (at s, seconds, generation): "
                     f"{[tuple(round(v, 4) for v in p) for p in top]}")
        for g in slow:
            self.run.log(f"slow group at {g[0]:.3f} s: {g[1]:.3f} s for "
                         f"{g[2]} requests (median {med:.3f} s)")

    # -- results -----------------------------------------------------------
    def e2e(self) -> dict:
        done = [r for r in self.records if math.isfinite(r[3])]
        out = {"lm_p95_ms": percentile([(r[3] - r[2]) * 1e3
                                        for r in self.records], 95)}
        if done:
            out["lm_tokens_per_s"] = (sum(r[4] for r in done)
                                      / (max(r[3] for r in done) - self.t0))
        return out

    def layer_inputs(self) -> dict:
        groups = self.stats["groups"] - self.base["groups"]
        responses = self.stats["responses"] - self.base["responses"]
        gen, p = self.t["generated_tokens"], self.t["prompt_tokens"]
        batch = responses / groups if groups else 0
        return {
            "kind": "lm",
            "groups": groups, "responses": responses,
            "batch": batch,
            "prompt_tokens": p, "generated_tokens": gen,
            # the decode steps of a group sit at positions p .. p+gen-2
            "decode_position": p + (gen - 2) / 2.0,
            "decode_cost": lmcost.decode_step,
        }

    def release(self):
        import jax

        self.engine.close()
        self.accel._params = None
        self.engine = self.accel = None
        gc.collect()
        live = sum(a.nbytes for a in jax.live_arrays())
        self.run.log(f"after release {live} bytes of arrays stay live")

    def check(self) -> list:
        done = sorted((r[0], r[1]) for r in self.records if math.isfinite(r[3]))
        if not done:
            return []
        rng = np.random.default_rng([self.run.seed, 7])
        pick = sample(rng, len(done), self.t["check_requests"])
        self.picked = [done[j] for j in pick]
        return [{"name": "lm_logit_gap", "value": self._widest(None),
                 "limit": self.c["limits"]["lm_logit_gap"]}]

    def _widest(self, quant, weights=None) -> float:
        if weights is None:
            weights = ref.make_weights(self.c, self.run.seed)
        widest = 0.0
        for key in self.picked:
            gaps = request_gaps(weights, self.c, self.prompt(*key),
                                self.served[key], quant=quant)
            widest = max(widest, float(gaps.max()))
        return widest

    def control(self) -> dict:
        """The reading of the control (the reference in float8) on the
        requests the check compared."""
        return {"lm_logit_gap": self._widest("fp8")}

    def calibrate(self, seeds) -> list:
        """[(seed, program's reading, control's reading)]: each seed's
        weights and first prompts through the set-up engine (the window's
        compiled programs), one group, in this one process."""
        out = []
        for seed in seeds:
            self.run.seed = int(seed)
            self.accel._params = None
            gc.collect()
            self.accel._params = self.program_weights(seed)
            keys = [(i, 0) for i in range(self.t["clients"])]
            futs = [self.engine.submit(self.prompt(*k), tier=self.t["tier"],
                                       gen=self.t["generated_tokens"]) for k in keys]
            for k, f in zip(keys, futs):
                self.served[k] = np.asarray(f.result(timeout=900)["tokens"])
            rng = np.random.default_rng([seed, 7])
            self.picked = [keys[j] for j in
                           sample(rng, len(keys), self.t["check_requests"])]
            # the program's weights go before the reference's are made
            self.accel._params = None
            gc.collect()
            weights = ref.make_weights(self.c, seed)
            out.append((int(seed), self._widest(None, weights),
                        self._widest("fp8", weights)))
            weights = None
            self.run.log(f"calibrate seed {seed}: program {out[-1][1]!r}, "
                         f"control {out[-1][2]!r}")
        return out

    def close(self):
        if getattr(self, "engine", None) is not None:
            self.engine.close()


def request_gaps(weights, c, prompt, served, *, quant=None):
    """Gaps of one request's served tokens under the reference; with
    ``quant`` the gaps of the tokens the quantized reference puts first
    at the same positions (the control)."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)[:-1]])
    start = len(prompt) - 1
    exact = ref.logits(weights, c, seq, start=start)
    if quant is None:
        return ref.token_gaps(exact, served)
    low = ref.logits(weights, c, seq, start=start, quant=quant)
    return ref.token_gaps(exact, low.argmax(axis=-1))
