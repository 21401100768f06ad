"""Qwen3: the Llama decoder with per-head RMSNorm on q and k, which its
configurations state as ``qk_norm``."""
from bench.archs.LlamaForCausalLM import *  # noqa: F401,F403
