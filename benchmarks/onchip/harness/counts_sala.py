"""Operations and bytes a MiniCPM-SALA stack (block-sparse attention beside
lightning attention) needs, from shapes alone: what the readers of the
``lightning_*`` and ``sparse_*`` rooflines and ``decode_step_hbm_roofline``
divide by a peak. Every count is the LEAST work the mathematics needs (a
lightning layer in its recurrent form, a sparse layer reading its chosen blocks
and nothing else), so a share of a roofline says how far the program is from
that, and cannot pass 100 %.

``cfg`` is a configuration file's dict (Hugging Face keys; the indexer's sizes
under ``sparse_config``). The lightning state is float32 and every other
tensor the serving dtype (``itemsize``), as the configuration's
``assumed.dtypes`` says.
"""

from __future__ import annotations

from functools import lru_cache

from .counts_hybrid import decode_chunk_runs, decode_chunk_step_s  # noqa: F401  (the readers' one name for it)

STATE_ITEMSIZE = 4


def has_both_kinds(cfg: dict) -> bool:
    return "lightning_nh" in cfg and "sparse_config" in cfg


knows = has_both_kinds


def _dims(cfg: dict):
    kinds = cfg["mixer_types"]
    return dict(D=cfg["hidden_size"], F=cfg["intermediate_size"], V=cfg["vocab_size"],
                H=cfg["num_attention_heads"], KV=cfg["num_key_value_heads"], HD=cfg["head_dim"],
                LH=cfg["lightning_nh"], E=cfg["lightning_head_dim"],
                n_sparse=sum(k == "minicpm4" for k in kinds), n_lightning=sum(k == "lightning-attn" for k in kinds))


# -- lightning layers -----------------------------------------------------------


def lightning_state_bytes(cfg: dict, slots: int) -> float:
    """One lightning layer's state of ``slots`` rows."""
    d = _dims(cfg)
    return float(slots * d["LH"] * d["E"] * d["E"] * STATE_ITEMSIZE)


def lightning_update_bytes(cfg: dict, slots: int, itemsize: int = 2) -> float:
    """Bytes one layer's decode update must move for the ``slots`` rows the
    program computes (all of the pool: static shapes): the state read and
    written, q, k, v in (serving dtype) and o out (float32, as the norm takes
    it)."""
    d = _dims(cfg)
    inner = d["LH"] * d["E"]
    return 2.0 * lightning_state_bytes(cfg, slots) + slots * inner * (3 * itemsize + 4)


def lightning_chunk_flops(cfg: dict, tokens: int) -> float:
    """One layer over ``tokens`` positions of one row, in the recurrent form
    (the least the mathematics needs): per token and head the update
    ``S = d S + k^T v`` and the read ``q S``, 2 E^2 multiply-adds."""
    d = _dims(cfg)
    return 4.0 * tokens * d["LH"] * d["E"] * d["E"]


def lightning_chunk_bytes(cfg: dict, tokens: int, itemsize: int = 2) -> float:
    """What that scan cannot avoid moving: q, k, v in, o out (float32), the
    row's state in and out."""
    d = _dims(cfg)
    inner = d["LH"] * d["E"]
    return tokens * inner * (3 * itemsize + 4) + 2.0 * lightning_state_bytes(cfg, 1)


# -- sparse layers --------------------------------------------------------------


def chosen_lanes(cfg: dict, position: int) -> int:
    """Lanes a query at ``position`` attends: everything up to itself below
    ``dense_len``, else ``topk`` blocks, its own cut at itself."""
    sc = cfg["sparse_config"]
    if position < sc["dense_len"]:
        return position + 1
    return (sc["topk"] - 1) * sc["block_size"] + position % sc["block_size"] + 1


def windows_seen(cfg: dict, position: int) -> int:
    """Compressed keys a query at ``position`` scores: the windows that end at
    or before it."""
    sc = cfg["sparse_config"]
    return max((position - sc["kernel_size"] + 1) // sc["kernel_stride"] + 1, 0)


def chosen_block_bytes(cfg: dict, live_slots: float, itemsize: int = 2) -> float:
    """Keys and values of the ``topk`` chosen blocks per kv-head of
    ``live_slots`` rows: what one sparse layer's decode kernel must read."""
    d, sc = _dims(cfg), cfg["sparse_config"]
    return live_slots * 2.0 * sc["topk"] * sc["block_size"] * d["KV"] * d["HD"] * itemsize


def sparse_decode_bytes(cfg: dict, live_slots: float, context_tokens: float, itemsize: int = 2) -> float:
    """One sparse layer's decode step over ``live_slots`` rows whose contexts
    sum to ``context_tokens`` (all past ``dense_len``): every row's compressed
    keys at its real length, and its chosen blocks."""
    d, sc = _dims(cfg), cfg["sparse_config"]
    row = d["KV"] * d["HD"] * itemsize
    return context_tokens / sc["kernel_stride"] * row + chosen_block_bytes(cfg, live_slots, itemsize)


def sparse_prefill_flops(cfg: dict, first: int, tokens: int) -> float:
    """One sparse layer over ``tokens`` queries from position ``first`` of one
    row: for a query past ``dense_len`` the indexer's scores against the
    compressed keys it sees, and for every query scores and weighted values
    over the lanes it attends (the chosen blocks only). 2 FLOPs a
    multiply-add."""
    d, sc = _dims(cfg), cfg["sparse_config"]
    per_lane = 2.0 * d["H"] * d["HD"]
    total = 0.0
    for t in range(first, first + tokens):
        if t >= sc["dense_len"]:
            total += per_lane * windows_seen(cfg, t)
        total += 2.0 * per_lane * chosen_lanes(cfg, t)
    return total


# -- the whole decode step --------------------------------------------------------


def weight_bytes_per_decode_step(cfg: dict, itemsize: int = 2) -> float:
    """Every layer's weights once in the serving dtype (q, the output gate and
    o at the inner width, k and v at the kv width, the MLP) and the untied head;
    the table's lookup reads a row a slot and is left out."""
    d = _dims(cfg)
    D, F = d["D"], d["F"]
    sparse = 3 * D * d["H"] * d["HD"] + 2 * D * d["KV"] * d["HD"] + 3 * D * F
    lightning = 5 * D * d["LH"] * d["E"] + 3 * D * F
    return float(itemsize * (d["n_sparse"] * sparse + d["n_lightning"] * lightning + D * d["V"]))


def decode_step_bytes(cfg: dict, slots: int, live_slots: float, context_tokens: float) -> float:
    """The whole decode step: weights once, the head, the chosen blocks and
    compressed keys of the live rows, the lightning state of every row in and
    out."""
    d = _dims(cfg)
    return (weight_bytes_per_decode_step(cfg)
            + d["n_sparse"] * sparse_decode_bytes(cfg, live_slots, context_tokens)
            + d["n_lightning"] * 2.0 * lightning_state_bytes(cfg, slots))


# -- the rows that decode ---------------------------------------------------------


def decoding_rows(run: dict) -> float | None:
    """Rows that DECODE in a dispatch of the window, on average: the tokens the
    window's dispatches emitted over the chunk's steps. Not the slots held
    (``run["occupancy"]``): a slot is held from admission, and with prompts of
    many chunks on one prefill lane a third of the held slots still wait for or
    ingest their prompt. A first token a finished prefill emits counts in (a
    thousandth), a token computed past a request's end does not
    (``decode_overshoot_pct``): the count stays at or under the rows computed."""
    toks = run.get("dispatch_tokens")
    return sum(toks) / len(toks) / run["decode_chunk_steps"] if toks else None


def decoding_context(run: dict) -> float | None:
    """Context tokens of those rows, summed: the held rows' average context
    (``dispatch_context`` over ``occupancy``: the harness counts a held row's
    whole prompt, ingested or not, and prompts are drawn alike for all) times
    the rows that decode."""
    rows, ctx, held = decoding_rows(run), run.get("dispatch_context"), run.get("occupancy")
    if not rows or not ctx or not held or not sum(held):
        return None
    return rows * sum(ctx) / sum(held)


def decode_step(run: dict) -> tuple[float, float] | None:
    """(bytes one decode step must move, traced seconds of one step) of a
    traced serving run, for ``decode_step_hbm_roofline``: ``decode_step_bytes``
    for the rows that decode (``decoding_rows``: not the slots held, of which
    some still ingest) at their contexts, the lightning state of every slot."""
    rows, context, step_s = decoding_rows(run), decoding_context(run), decode_chunk_step_s(run)
    if not step_s or not rows or not context:
        return None
    return decode_step_bytes(run["cell"]["config"], run["slots"], rows, context), step_s


# -- the trace ----------------------------------------------------------------------


def prefill_chunks(parsed: dict) -> list[tuple[int, int]]:
    """(chunk index within its prompt, tokens) of every traced prefill chunk,
    from the ``tpu_engine.batcher.prefill`` annotations."""
    return [(int(args["chunk"]), int(args["tokens"])) for *_, phase, args in parsed["annotations"]
            if phase == "batcher.prefill" and "tokens" in args and "chunk" in args]


@lru_cache(maxsize=8)
def _seconds_under(xplane: str, names: tuple) -> float | None:
    from jax.profiler import ProfileData

    from . import program_trace, trace_reduce

    with open(xplane, "rb") as f:
        raw = f.read()
    scopes = program_trace.op_scopes(raw)
    planes = trace_reduce._device_planes(ProfileData.from_serialized_xspace(raw))
    line = trace_reduce._line(planes[0], trace_reduce.OPS_LINE) if planes else None
    if line is None:
        return None
    took, found = 0.0, False
    for ev in line.events:
        if trace_reduce.CONTAINER.match(ev.name):
            continue
        if set(names) <= set(program_trace.scope_path(scopes.get(ev.name, ""))):
            took, found = took + ev.duration_ns, True
    return took / 1e9 if found else None


def seconds_under(run: dict, *names: str) -> float | None:
    """Device seconds of the first chip's ops whose path holds ALL of
    ``names`` (a program's name and a scope: ``"decode_chunk", "sparse_attn"``);
    None where the run was not traced or no op lies under them."""
    from . import program_trace

    if not run.get("trace"):
        return None
    path = program_trace.find_xplane(run["cell"]["cell"]["name"])
    return _seconds_under(path, tuple(names)) if path else None
