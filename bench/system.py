"""The system under test, seen from the benchmark: the program's paged
``ContinuousEngine`` built by ``repro.launch.serve.make_engine`` on a
``repro.models.transformer.Model``, driven through ``submit`` and
``step_chunk``.

This is the one module of the benchmark that imports the program.  Beyond
the public surface it reads the scheduler's per-request outputs after each
boundary (the engine has no token stream) and the scheduler's slot count,
and it warms the engine's eager prompt padding once per prompt length.
"""
from __future__ import annotations

import jax
import numpy as np

from bench import weights as W

# the jitted programs' names as they appear in a device trace
DECODE_PROGRAM = "chunk_fn"
PREFILL_PROGRAM = "fn"


def model_config(cfg: dict):
    from repro.models.common import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=W.head_dim(cfg), qk_norm=bool(cfg.get("qk_norm")),
        qkv_bias=bool(cfg.get("attention_bias")),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=cfg["torch_dtype"],
        max_seq=cfg["engine"]["max_seq"])


def to_program(w: dict, cfg: dict) -> dict:
    """The benchmark's weights as the program's parameter tree."""
    from repro.models.attention import AttnParams
    from repro.models.ffn import MlpParams
    from repro.models.transformer import BlockParams
    L = w["layers"]
    qk = bool(cfg.get("qk_norm"))
    attn = AttnParams(L["wq"], L["wk"], L["wv"], L["wo"], None, None, None,
                      L["q_norm"] if qk else None, L["k_norm"] if qk else None)
    return {"embed": w["embed"], "ln_f": w["ln_f"], "head": w["head"],
            "blocks": BlockParams(L["ln1"], attn, L["ln2"],
                                  MlpParams(L["w_gate"], L["w_up"],
                                            L["w_down"]))}


class Engine:
    """One engine with its weights, made on the device from the seed in
    one jitted call, in the served dtype."""

    def __init__(self, cfg: dict, seed: int):
        from repro.launch.serve import make_engine
        from repro.models.transformer import Model
        e = cfg["engine"]
        if e["block_size"] != 16:
            raise ValueError("make_engine pages in blocks of 16 positions")
        self.model = Model(model_config(cfg))
        dtype = jax.numpy.dtype(cfg["torch_dtype"])
        make = jax.jit(lambda key: to_program(
            W.all_weights(key, cfg, dtype), cfg))
        params = make(W.seed_key(seed))
        jax.block_until_ready(params)
        self.eng = make_engine(self.model, params, "continuous",
                               max_seq=e["max_seq"], slots=e["slots"],
                               chunk=e["chunk"], kv_layout="paged",
                               kv_blocks=e["kv_blocks"])
        self.chunk = e["chunk"]
        self.slots = e["slots"]

    def warm(self, prompt_lengths) -> dict:
        """Compile every shape the traffic reaches: one request per prefill
        bucket (each decoding one chunk), then the prompt padding of each
        length.  Returns the jit counts the engine reports."""
        from repro.serve.engine import Request
        from repro.serve.scheduler import pick_bucket
        buckets = {}
        for n in prompt_lengths:
            buckets.setdefault(pick_bucket(n, self.eng.buckets), n)
        reqs = [Request(prompt=np.zeros(n, np.int32),
                        max_new_tokens=self.chunk + 1)
                for n in buckets.values()]
        self.eng.run(reqs)            # marks the engine warm when done
        for n in prompt_lengths:
            b = pick_bucket(n, self.eng.buckets)
            jax.block_until_ready(
                self.eng._pad_prompt(np.zeros(n, np.int32), b)[None])
        return self.jit_counts()

    def jit_counts(self) -> dict:
        s = self.eng.stats()
        return {k: s[k] for k in ("decode_compiles", "prefill_entries",
                                  "recompiles_after_warm")}

    # -- what the loop drives ------------------------------------------------

    def submit(self, prompt, max_new: int) -> int:
        from repro.serve.engine import Request
        return self.eng.submit(Request(prompt=prompt,
                                       max_new_tokens=int(max_new)))

    def step(self):
        return self.eng.step_chunk()

    def count(self, rid: int) -> int:
        return len(self.eng.sched.outputs.get(rid, ()))

    def state(self, rid: int):
        done = self.eng.sched.done.get(rid)
        return done[0] if done else None

    def tokens(self, rid: int):
        return list(self.eng.sched.outputs.get(rid, ()))

    def busy_share(self) -> float:
        return self.eng.sched.stats()["busy"] / self.slots

    def free(self) -> None:
        """Delete the weights and the engine's device state, so the
        reference that runs next has the chip to itself."""
        e, self.eng = self.eng, None
        for tree in (e.params, e.cache, e.tokens, e.pos, e.keys, e.temps,
                     e.top_ks, e.block_tables, e._zero_staging):
            for a in jax.tree_util.tree_leaves(tree):
                if isinstance(a, jax.Array) and not a.is_deleted():
                    a.delete()
