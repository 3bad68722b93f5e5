"""One cell's program objects: the configuration as it is run, the model,
and the keywords its ``ServeEngine`` is built with."""
from __future__ import annotations

# published config.json key -> the program's ArchConfig field
HF_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
           "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
           "num_hidden_layers": "n_layers", "vocab_size": "vocab",
           "rope_theta": "rope_theta",
           "tie_word_embeddings": "tie_embeddings"}


def program_config(conf: dict):
    """The program's ArchConfig for a configuration file; refuses a file
    whose published keys disagree with what the program would run."""
    from repro.configs import get_config
    cfg = get_config(conf["arch"]).scaled(**conf["program"])
    for key, field in HF_KEYS.items():
        if conf[key] != getattr(cfg, field):
            raise ValueError(f"{conf['name']}: {key}={conf[key]} but the "
                             f"program runs {field}={getattr(cfg, field)}")
    return cfg


def engine_kwargs(conf: dict, mix: dict, max_len: int) -> dict:
    """ServeEngine keywords: the config's engine settings, the mix's slots
    and step budget, and ``max_len`` rounded up to whole pages."""
    kw = dict(conf["engine"])
    page = kw["page_size"]
    kw.update(n_slots=mix["n_slots"], max_batch_tokens=mix["max_batch_tokens"],
              max_len=-(-max_len // page) * page)
    return kw
