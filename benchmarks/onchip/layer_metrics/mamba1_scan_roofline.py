"""Kernels: the least HBM time the Mamba-1 scans of the traced prefill chunks
needed — per Mamba-1 layer and chunk its inputs, output and the row's state in
and out (``counts_phi4flash.mamba1_chunk_bytes``) over peak HBM bytes/s, at the
chunk's own length (the ``tokens=`` of its ``tpu_engine.batcher.prefill``
annotation; of the annotated chunks those that the device's side of the trace
holds a run of a prefill program for) — over the traced device time under the
``mamba1_scan`` scope. The
scan is bound by the VECTOR unit, not by these bytes (an exponential and three
multiply-adds for each of 168 M (position, channel, state) triples a chunk), and
``peaks.py`` holds no peak for it: the share says how far the scan is from
costing no more than its traffic, and a position-by-position loop reads in
single digits here."""

from harness import counts_phi4flash as counts
from harness import program_trace
from harness.peaks import peaks


def read(run, name):
    parsed = program_trace.of_run(run)
    cfg = run["cell"]["config"]
    if not parsed or run["device"]["platform"] != "tpu" or not counts.knows(cfg):
        return None
    took = parsed["scopes"]["by_scope"].get("mamba1_scan")
    # The host's side of a trace can outlast the device's (this cell's does): the chunks whose scans the
    # device's side holds are the first as many as it holds runs of a prefill program.
    held = sum(len(v) for k, v in run["trace"]["module_runs"].items() if k.startswith("jit_prefill"))
    chunks = counts.prefill_chunks(parsed)[:held]
    if not took or not chunks:
        return None
    need = sum(counts.mamba1_chunk_bytes(cfg, t) for _, t in chunks) / peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * counts.n_layers(cfg, "mamba") * need / took
