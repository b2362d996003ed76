"""Operations and bytes a Brumby stack (every mixer a gated power-retention
layer of degree 2) needs, from shapes alone: what the readers of the
``power_*`` rooflines and ``decode_step_hbm_roofline`` divide by a peak. Every
count is the LEAST the mathematics needs in the layer's recurrent form (a
token's state update and one read of it per query head), so a share of a
roofline says how far the program is from that, and cannot pass 100 %.

``cfg`` is a configuration file's dict (Hugging Face keys; the retention
layer's settings beside them). The state is float32 and every other tensor the
serving dtype (``itemsize``), as the configuration's ``assumed`` says. A decode
step's bytes do not depend on the rows that decode or on their contexts: the
program computes every slot of the pool, and the state has no lane.
"""

from __future__ import annotations

from .counts_hybrid import decode_chunk_runs, decode_chunk_step_s  # noqa: F401  (the readers' one name for it)
from .counts_sala import prefill_chunks  # noqa: F401

STATE_ITEMSIZE = 4


def knows(cfg: dict) -> bool:
    return cfg.get("model_type") == "brumby"


def _dims(cfg: dict) -> dict:
    HD, tile = cfg["head_dim"], cfg["power_tile"]
    n = HD // tile
    return dict(D=cfg["hidden_size"], F=cfg["intermediate_size"], V=cfg["vocab_size"],
                H=cfg["num_attention_heads"], KV=cfg["num_key_value_heads"], HD=HD,
                W=n * (n + 1) // 2 * tile * tile, L=cfg["num_hidden_layers"])


def n_layers(cfg: dict) -> int:
    return _dims(cfg)["L"]


def power_state_bytes(cfg: dict, slots: int) -> float:
    """One layer's state of ``slots`` rows: per kv-head the expanded key's W
    coordinates by HD values and the normaliser."""
    d = _dims(cfg)
    return float(slots * d["KV"] * d["W"] * (d["HD"] + 1) * STATE_ITEMSIZE)


def _token_io_bytes(cfg: dict, itemsize: int) -> float:
    """What a position brings to and takes from the mixer's core: q, k, v in
    (serving dtype), the gate's log in and the normalised output out (float32)."""
    d = _dims(cfg)
    return (d["H"] + 2 * d["KV"]) * d["HD"] * itemsize + d["KV"] * 4 + d["H"] * d["HD"] * 4


def power_update_bytes(cfg: dict, slots: int, itemsize: int = 2) -> float:
    """Bytes one layer's decode update must move for the ``slots`` rows the
    program computes (all of the pool: static shapes): the state read and
    written, and each row's q, k, v, gate and output."""
    return 2.0 * power_state_bytes(cfg, slots) + slots * _token_io_bytes(cfg, itemsize)


def power_chunk_flops(cfg: dict, tokens: int) -> float:
    """One layer over ``tokens`` positions of one row in the recurrent form:
    per token the update of each kv-head's state and normaliser and one read
    of them per query head, 2 FLOPs a multiply-add."""
    d = _dims(cfg)
    return 2.0 * tokens * (d["KV"] + d["H"]) * d["W"] * (d["HD"] + 1)


def power_chunk_bytes(cfg: dict, tokens: int, itemsize: int = 2) -> float:
    """What that scan cannot avoid moving: each position's q, k, v, gate and
    output, the row's state in and out."""
    return tokens * _token_io_bytes(cfg, itemsize) + 2.0 * power_state_bytes(cfg, 1)


def weight_bytes_per_decode_step(cfg: dict, itemsize: int = 2) -> float:
    """Every layer's weights once in the serving dtype (q and o at the query
    width, k and v at the kv width, the gate, the MLP) and the untied head; the
    table's lookup reads a row a slot and is left out."""
    d = _dims(cfg)
    D = d["D"]
    layer = 2 * D * d["H"] * d["HD"] + 2 * D * d["KV"] * d["HD"] + D * d["KV"] + 3 * D * d["F"]
    return float(itemsize * (d["L"] * layer + D * d["V"]))


def decode_step_bytes(cfg: dict, slots: int) -> float:
    """The whole decode step: every weight once with the head, each layer's
    state of every slot in and out."""
    return weight_bytes_per_decode_step(cfg) + _dims(cfg)["L"] * 2.0 * power_state_bytes(cfg, slots)


def decode_step(run: dict) -> tuple[float, float] | None:
    """(bytes one decode step must move, traced seconds of one step) of a
    traced serving run, for ``decode_step_hbm_roofline``: the same bytes
    whatever rows decode, over the median traced run of the decode program
    divided by the chunk's steps."""
    step_s = decode_chunk_step_s(run)
    if not step_s:
        return None
    return decode_step_bytes(run["cell"]["config"], run["slots"]), step_s
