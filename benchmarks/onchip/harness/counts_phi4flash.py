"""Operations and bytes a Phi-4-mini-flash stack needs, from shapes alone: what
the readers of ``shared_kv_decode_roofline``, the ``mamba1_*`` rooflines and
``decode_step_hbm_roofline`` divide by a peak. Every count is the LEAST the
mathematics needs, whatever implements it: the one full-attention cache read
once per reader for the live rows at their real lengths, a window layer's
newest ``sliding_window`` lanes, a Mamba-1 state in and out, every weight once.
A program that reads every lane of every slot reads under 100 % by that much.

``cfg`` is a configuration file's dict (Hugging Face keys; the Mamba sizes the
published config lacks under ``assumed_sizes``). The Mamba state is float32 and
every other tensor the serving dtype (``itemsize``), as the configuration's
``assumed.dtypes`` says.
"""

from __future__ import annotations

import bisect
from functools import lru_cache

from .counts_hybrid import decode_chunk_runs  # noqa: F401  (the readers' one name for it)
from .counts_sala import prefill_chunks  # noqa: F401

STATE_ITEMSIZE = 4
CLOCK_SLACK_NS = 5e6


def knows(cfg: dict) -> bool:
    return cfg.get("model_type") == "phi4flash"


def _dims(cfg: dict) -> dict:
    D, a, kinds = cfg["hidden_size"], cfg["assumed_sizes"], cfg["layer_types"]
    n = {k: sum(t == k for t in kinds) for k in ("mamba", "sliding_attention", "full_attention", "cross_attention", "gmu")}
    return dict(D=D, F=cfg["intermediate_size"], V=cfg["vocab_size"], H=cfg["num_attention_heads"],
                KV=cfg["num_key_value_heads"], HD=D // cfg["num_attention_heads"], I=a["mamba_expand"] * D,
                N=a["mamba_d_state"], K=a["mamba_d_conv"], R=a["mamba_dt_rank"], W=cfg["sliding_window"], n=n)


def n_layers(cfg: dict, kind: str) -> int:
    return _dims(cfg)["n"][kind]


def kv_row_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Keys and values one token keeps in one attention layer."""
    d = _dims(cfg)
    return 2 * d["KV"] * d["HD"] * itemsize


def shared_readers(cfg: dict) -> int:
    """Layers that read the ONE full cache in a decode step: the full-attention
    layer itself and every cross-attention layer."""
    d = _dims(cfg)
    return d["n"]["full_attention"] + d["n"]["cross_attention"]


def shared_kv_decode_bytes(cfg: dict, context_tokens: float, itemsize: int = 2) -> float:
    """One reader's decode step: the live rows' keys and values at their real
    lengths (``context_tokens``: their sum), once."""
    return context_tokens * kv_row_bytes(cfg, itemsize)


def window_decode_bytes(cfg: dict, rows: float, context_tokens: float, itemsize: int = 2) -> float:
    """One window layer's decode step: each live row's newest ``sliding_window``
    lanes (all of a shorter row)."""
    d = _dims(cfg)
    return min(context_tokens, rows * d["W"]) * kv_row_bytes(cfg, itemsize)


def mamba1_state_bytes(cfg: dict, slots: int) -> float:
    d = _dims(cfg)
    return float(slots * d["N"] * d["I"] * STATE_ITEMSIZE)


def mamba1_update_bytes(cfg: dict, slots: int, itemsize: int = 2) -> float:
    """Bytes one layer's decode update must move for the ``slots`` rows the
    program computes (all of the pool: static shapes): the state read and
    written, u (serving dtype), dt (float32), B and C in, y out (float32)."""
    d = _dims(cfg)
    return 2.0 * mamba1_state_bytes(cfg, slots) + slots * (d["I"] * (itemsize + 4 + 4) + 2 * d["N"] * itemsize)


def mamba1_chunk_bytes(cfg: dict, tokens: int, itemsize: int = 2) -> float:
    """What one layer's scan over ``tokens`` positions of one row cannot avoid
    moving: u (serving dtype), dt (float32), B and C in, y out (float32), the
    row's state in and out. The scan itself is bound by the vector unit (an
    exponential and three multiply-adds per (position, channel, state), 168 M
    of them a chunk of 2 048), for which ``peaks.py`` holds no peak: a share of
    THIS count says how far the scan is from being free, not from its own bound."""
    d = _dims(cfg)
    return tokens * (d["I"] * (itemsize + 4 + 4) + 2 * d["N"] * itemsize) + 2.0 * mamba1_state_bytes(cfg, 1)


def weight_bytes_per_decode_step(cfg: dict, itemsize: int = 2) -> float:
    """Every weight once in the serving dtype: the Mamba-1 mixers (in_proj over
    u | z, the convolution, x_proj, dt_proj, out_proj), the attention layers'
    projections (q, k, v, o; the cross layers' q and o), the gated memory units'
    two, an MLP after each, and the tied head (the table, read whole as the
    head; the lookup reads a row a slot of it)."""
    d = _dims(cfg)
    D, F, I, n = d["D"], d["F"], d["I"], d["n"]
    inner, kv = d["H"] * d["HD"], d["KV"] * d["HD"]
    mamba = 2 * D * I + (d["K"] + 1) * I + I * (d["R"] + 2 * d["N"]) + d["R"] * I + I * D
    attn = 2 * D * inner + 2 * D * kv
    layers = sum(n.values())
    return float(itemsize * (n["mamba"] * mamba + (n["sliding_attention"] + n["full_attention"]) * attn
                             + n["cross_attention"] * 2 * D * inner + n["gmu"] * 2 * D * I
                             + layers * 3 * D * F + D * d["V"]))


def decode_step_bytes(cfg: dict, slots: int, rows: float, context_tokens: float, itemsize: int = 2) -> float:
    """The whole decode step: every weight once with the table once, the one
    full cache at the live rows' real lengths for each of its readers, the
    window layers' newest lanes, the Mamba-1 state (and convolution state) of
    every slot in and out."""
    d = _dims(cfg)
    conv = slots * (d["K"] - 1) * d["I"] * itemsize
    return (weight_bytes_per_decode_step(cfg, itemsize)
            + shared_readers(cfg) * shared_kv_decode_bytes(cfg, context_tokens, itemsize)
            + d["n"]["sliding_attention"] * window_decode_bytes(cfg, rows, context_tokens, itemsize)
            + d["n"]["mamba"] * 2.0 * (mamba1_state_bytes(cfg, slots) + conv))


# -- the traced runs of the decode program, each against what it decoded --------------
#
# A closed-loop run's trace starts BEFORE the fill (85 prefill chunks, a decode
# dispatch with the rows admitted so far between every two), and this cell's
# device trace ENDS INSIDE the fill: the position-by-position Mamba-1 scan makes
# some hundred thousand op events a chunk, and the device's side of the trace is
# full after about 18 s (78 decode runs and the 30th admission, where the host's
# side goes on to the window's sixth second; my chip run, PR 43). So no run of
# the decode program in the trace belongs to the measured window, and a count
# taken at the window's contexts may not be held against it (27 such runs, of
# 22-30 rows, read 99.98 % so). What a decode step must read depends on the
# rows that decode and on their lengths, and the program says both on the
# annotation of the phase that waits for the run (``tpu_engine.batcher.device``:
# ``rows=``, ``context=``). Every traced run is held against its own.


@lru_cache(maxsize=4)
def _traced_decode(xplane: str) -> list[dict]:
    from jax.profiler import ProfileData

    from . import program_trace, trace_reduce

    with open(xplane, "rb") as f:
        raw = f.read()
    data = ProfileData.from_serialized_xspace(raw)
    planes = trace_reduce._device_planes(data)
    if not planes:
        return []
    mods, ops = (trace_reduce._line(planes[0], name) for name in (trace_reduce.MODULES_LINE, trace_reduce.OPS_LINE))
    waits = [(t0, t1, args) for t0, t1, phase, args in program_trace.annotations(data)
             if phase == "batcher.device" and "rows" in args and "context" in args]
    if mods is None or ops is None or not waits:
        return []
    began = [t0 for t0, *_ in waits]
    runs = []
    for ev in mods.events:
        if "decode_chunk" not in ev.name:
            continue
        end = ev.start_ns + ev.duration_ns
        i = bisect.bisect_right(began, end) - 1  # the wait the run ended in (the clocks of host and chip differ a little)
        if i >= 0 and end <= waits[i][1] + CLOCK_SLACK_NS:
            runs.append({"t0": ev.start_ns, "t1": end, "s": ev.duration_ns / 1e9, "by_scope": {},
                         "rows": int(waits[i][2]["rows"]), "context": int(waits[i][2]["context"])})
    runs.sort(key=lambda r: r["t0"])
    starts = [r["t0"] for r in runs]
    scopes = program_trace.op_scopes(raw)
    for ev in ops.events:
        i = bisect.bisect_right(starts, ev.start_ns) - 1
        if i < 0 or ev.start_ns >= runs[i]["t1"] or trace_reduce.CONTAINER.match(ev.name):
            continue
        by_scope = runs[i]["by_scope"]
        for scope in set(program_trace.scope_path(scopes.get(ev.name, ""))):
            by_scope[scope] = by_scope.get(scope, 0.0) + ev.duration_ns / 1e9
    return runs


def traced_decode(run: dict) -> list[dict]:
    """Every run of the decode program on the first chip's trace whose end lies
    in a ``tpu_engine.batcher.device`` annotation that says what it decoded:
    {"s": device seconds, "rows": the rows that decode, "context": the lanes
    they hold, summed, as the dispatch starts, "by_scope": seconds of the run's
    ops under each ``jax.named_scope``}, in time order. Empty where the run was
    not traced or the program says neither (the parent's)."""
    from . import program_trace

    if not run.get("trace"):
        return []
    path = program_trace.find_xplane(run["cell"]["cell"]["name"])
    return _traced_decode(path) if path else []


def lanes_read(traced: dict, steps: int) -> float:
    """The lanes the rows of one traced run hold at a step of its chunk, summed
    over the rows and averaged over the chunk's ``steps`` (a row grows by one
    lane a step)."""
    return traced["context"] + traced["rows"] * (steps - 1) / 2.0


def decode_step(run: dict) -> tuple[float, float] | None:
    """(bytes one decode step must move, traced seconds of one step) of a
    traced serving run, for ``decode_step_hbm_roofline``: over every traced run
    of the decode program, ``decode_step_bytes`` for the rows IT decoded at the
    lengths THEY had, and its own device time over the chunk's steps; both are
    means over those runs' steps, so the share is all their bytes over all
    their time."""
    runs, steps = traced_decode(run), run["decode_chunk_steps"]
    if not runs:
        return None
    cfg, slots = run["cell"]["config"], run["slots"]
    need = sum(decode_step_bytes(cfg, slots, r["rows"], lanes_read(r, steps)) for r in runs) / len(runs)
    return need, sum(r["s"] for r in runs) / len(runs) / steps
