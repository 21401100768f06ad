"""Small configurations of the real cells for the benchmark's CPU tests:
the same code paths and checks at smoke widths, in float32."""
import copy

SMOKE_WIDTHS = dict(hidden_size=64, intermediate_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16, vocab_size=256)


def smoke_cell(name: str, dtype: str = "float32") -> dict:
    """The cell ``name`` at smoke widths, a 256-position engine and short
    traffic: same code paths, same check, a size the CPU can run."""
    from bench import spec
    c = copy.deepcopy(spec.cell(name))
    cfg = c["config"]
    cfg.update(SMOKE_WIDTHS, torch_dtype=dtype)
    cfg["engine"].update(max_seq=256, slots=4, kv_blocks=4 * 16)
    t = c["traffic"]
    t["prompt"].update(min=16, max=120, median=40)
    t["output"].update(min=8, max=64)
    if t["output"]["dist"] == "lognormal":
        t["output"]["median"] = 24
    t["warmup_s"] = 0.3
    if t["kind"] == "closed_loop":
        t["clients"] = 6
    else:
        t["rate"] = 6.0
    c["check"].update(sample=3)
    return c


CPU_PEAKS = {"flops": 1e12, "hbm_bytes_per_s": 1e11}
