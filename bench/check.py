"""What decides ``correct``, and what a traced run keeps for its readers.

Two numbers, over a sample of the requests the window finished, each
against the configuration's limit (``correct`` in the configuration
file, which also holds the readings each limit was set from):

- ``served_gap_max``: every served token is greedy, so it should be the
  reference's best at its position; this is the widest gap by which a
  served token's reference logit lies below the reference's best logit
  there, the reference run once over each prompt and its served tokens.
- ``kv_err_max``: the cache rows the timed path wrote into the paged
  cache for each sampled prompt (its full blocks, found through the
  prefix index; for grouped-KV attention the keys and values, each
  architecture's ``cache_leaves``), against the reference's: per layer
  and leaf, the largest absolute difference over the largest reference
  magnitude; the worst of them.  It reads every layer below the last,
  at every prompt position, and so sees a change of precision that no
  greedy token shows.
"""
from __future__ import annotations

import numpy as np


def program_kv(c: dict, engine, prompt) -> list:
    """Each layer's cache rows that the engine wrote for the full blocks
    of ``prompt``, read back from its paged pool: one tuple a layer, of
    the architecture's ``cache_leaves`` (for grouped-KV attention K and
    V, each of shape (n, kv_heads, head_dim)); empty when no block is
    cached."""
    import jax
    import jax.numpy as jnp

    import archs
    from repro.serve.kv_cache import _chain_digest
    leaves = archs.program(c["model_type"]).cache_leaves
    kv = engine.kv
    bs = kv.block_size
    prompt = np.asarray(prompt, np.int32)
    digest, blocks = b"", []
    for i in range((len(prompt) - 1) // bs):
        digest = _chain_digest(digest, prompt[i * bs:(i + 1) * bs])
        b = kv._hash_to_block.get(digest)
        if b is None:
            break
        blocks.append(b)
    if not blocks:
        return []
    idx = jnp.asarray(blocks, jnp.int32)
    pools = kv.pools
    layers = [tuple(p["kv"][n][idx] for n in leaves)
              for p in pools.get("prefix", [])]
    body = pools["body"]["b0"]["kv"]
    layers += [tuple(body[n][g][idx] for n in leaves)
               for g in range(body[leaves[0]].shape[0])]
    out = jax.device_get(layers)
    return [tuple(a.reshape((-1,) + a.shape[2:]) for a in layer)
            for layer in out]


def kv_err(got: list, ref: list, keep: np.ndarray) -> float:
    """Worst layer and leaf's largest |got - ref| at the kept positions,
    over that leaf's largest |ref|.  Raises ``ValueError`` where the
    program and the reference hold different numbers of layers or of
    leaves a layer, so that no part of the cache goes unjudged."""
    worst = 0.0
    for got_layer, ref_layer in zip(got, ref, strict=True):
        for g, r in zip(got_layer, ref_layer, strict=True):
            if keep.any():
                worst = max(worst, float(np.max(np.abs(g - r)[keep])
                                         / np.max(np.abs(r))))
    return worst


def readings(c: dict, params, picked, kv_read, *, control=False) -> dict:
    """The numbers compared, for the program; with ``control``, also for
    the reference one precision lower in its place, and for a served
    token altered (each served token + 1).  Positions where the
    reference's routing is a near-tie (``router_tie``) are left out.
    The candidates are taken in order until the tokens and KV rows
    checked reach the sample's minimums; once the tokens do, a candidate
    whose KV the pool no longer holds is passed over."""
    import reference
    tie = c["correct"]["router_tie"]
    need = c["correct"]["sample"]
    out = {"served_gap_max": 0.0, "kv_err_max": 0.0, "checked_tokens": 0,
           "checked_kv_rows": 0, "near_ties": 0, "requests": 0}
    if control:
        out.update(control_served_gap_max=0.0, control_kv_err_max=0.0,
                   altered_served_gap_max=0.0)
    V = c["vocab_size"]
    for r, got in zip(picked, kv_read):
        tokens_met = out["checked_tokens"] >= need["min_tokens"]
        if tokens_met and out["checked_kv_rows"] >= need["min_kv_rows"]:
            break
        if tokens_met and not got:
            continue
        out["requests"] += 1
        seq = reference.Sequence(c, params, r.prompt, r.out,
                                 control=control)
        keep = ~seq.served_near_tie(tie)
        out["near_ties"] += int(np.sum(~keep))
        out["checked_tokens"] += int(np.sum(keep))
        if keep.any():
            out["served_gap_max"] = max(
                out["served_gap_max"],
                float(np.max(seq.served_gaps()[keep])))
        if control and keep.any():
            out["control_served_gap_max"] = max(
                out["control_served_gap_max"],
                float(np.max(seq.control_gaps()[keep])))
            altered = (np.asarray(r.out) + 1) % V
            out["altered_served_gap_max"] = max(
                out["altered_served_gap_max"],
                float(np.max(seq.served_gaps(altered)[keep])))
        if got:
            n = got[0][0].shape[0]
            ref = seq.prompt_kv(n)
            kv_keep = ~seq.near_tie(tie)[:n]
            out["kv_err_max"] = max(out["kv_err_max"],
                                    kv_err(got, ref, kv_keep))
            out["checked_kv_rows"] += int(np.sum(kv_keep))
            if control:
                out["control_kv_err_max"] = max(
                    out["control_kv_err_max"],
                    kv_err(seq.prompt_kv(n, control=True), ref, kv_keep))
    return out


def verdict(c: dict, values: dict) -> tuple:
    """(correct, compared): each number beside its limit."""
    lim = c["correct"]
    compared = [
        {"name": "served_gap_max", "value": values["served_gap_max"],
         "limit": lim["served_gap_max"]},
        {"name": "kv_err_max", "value": values["kv_err_max"],
         "limit": lim["kv_err_max"]},
        {"name": "checked_tokens", "value": values["checked_tokens"],
         "limit": lim["sample"]["min_tokens"]},
        {"name": "checked_kv_rows", "value": values["checked_kv_rows"],
         "limit": lim["sample"]["min_kv_rows"]}]
    ok = (values["served_gap_max"] <= lim["served_gap_max"]
          and values["kv_err_max"] <= lim["kv_err_max"]
          and values["checked_tokens"] >= lim["sample"]["min_tokens"]
          and values["checked_kv_rows"] >= lim["sample"]["min_kv_rows"])
    return ok, compared


class TraceWindow:
    """The profiler runs from just before the window opens to its close;
    the window itself is marked (``bench.traced``) on the profiler's own
    clock."""

    def __init__(self, logdir):
        self.logdir = str(logdir)
        self.begin = self.end = None

    def start(self):
        import jax
        jax.profiler.start_trace(self.logdir)

    def open(self):
        import time

        import jax
        self._ann = jax.profiler.TraceAnnotation("bench.traced")
        self._ann.__enter__()
        self.begin = time.perf_counter()

    def close(self):
        import time

        import jax
        self.end = time.perf_counter()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()


def steps_and_spans(tap, engine, window: TraceWindow, kernels) -> dict:
    """The traced run's records: each step's rows, positions, emitted
    tokens and plan statistics; the engine's host spans; the trace
    reduced over the traced span."""
    import jax

    import trace_reduce
    steps = [s for s in tap.steps if window.begin <= s.start <= window.end]
    pos, aux = jax.device_get(([s.pos for s in steps],
                               [s.aux for s in steps]))
    rows = [{"rows": s.rows, "emitted": s.emitted, "pos": np.asarray(p),
             **{k: float(v) for k, v in a.items()}}
            for s, p, a in zip(steps, pos, aux)]
    tracer = engine.obs.tracer
    spans = [e for e in tracer.events if e.get("ph") == "X"]
    ev = trace_reduce.events(trace_reduce.find_xplane(window.logdir))
    traced = [h for h in ev["host"] if h[0] == "bench.traced"]
    if not traced:
        raise RuntimeError("the trace holds no bench.traced span")
    _, t_a, dur = traced[0]
    red = trace_reduce.reduce(ev, t_a, t_a + dur, kernels=kernels)
    return {"steps": rows, "spans": spans, "trace": red,
            "traced": (window.begin, window.end),
            "tracer_t0": tracer._t_origin}
