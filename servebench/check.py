"""Whether the timed path served correct tokens.

After the window has closed and the program's state is freed, a sample of
the requests it finished, drawn from the seed and holding the one with the
most served tokens, is run through the plain reference: each prompt with
its served tokens, in float32. For every served (greedy) token the gap by
which its reference logit lies below the reference's best is read; the
widest gap is compared with the cell's limit. The control puts the same
reference, computed in float8, in the program's place: at each position
it reads the gap of the token that float8 puts first.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

SAMPLE_REQUESTS = 8        # sequences in the reference's one batch
SAMPLE_TOKENS = 512        # served tokens to reach before stopping short
LEN_BUCKET = 512           # sequence lengths round up to this
ROW_CHUNK = 256            # logit rows per head product
NOTHING = 1e9              # the gap read when no served token was compared


def sample(finished: List, running: List, seed: int) -> List:
    """Requests to compare: the finished one with the most served tokens,
    then other finished ones in an order drawn from ``seed``, until
    ``SAMPLE_TOKENS`` served tokens or ``SAMPLE_REQUESTS`` requests are
    reached. Where the window finished too few (long outputs), requests
    still running top the sample up, in an order drawn from ``seed``:
    their tokens were streamed to the user all the same."""
    rng = np.random.default_rng([int(seed), 0xC4EC])
    out: List = []
    n = 0
    pools = [finished, [r for r in running if r.tokens]]
    for k, pool in enumerate(pools):
        if not pool:
            continue
        order = [pool[int(i)] for i in rng.permutation(len(pool))]
        if k == 0:
            longest = max(pool, key=lambda r: (len(r.tokens), -r.index))
            order = [longest] + [r for r in order if r is not longest]
        for r in order:
            if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_REQUESTS:
                return out
            out.append(r)
            n += len(r.tokens)
    return out


def _batch(samples: List):
    seqs = [np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
            for r in samples]
    width = LEN_BUCKET * -(-max(len(s) for s in seqs) // LEN_BUCKET)
    tokens = np.zeros((SAMPLE_REQUESTS, width), np.int32)
    rows, served = [], []
    for i, (r, s) in enumerate(zip(samples, seqs)):
        tokens[i, :len(s)] = s
        p = len(r.prompt)
        rows += [(i, p - 1 + j) for j in range(len(r.tokens))]
        served += list(r.tokens)
    return tokens, np.asarray(rows, np.int32), np.asarray(served, np.int32)


@jax.jit
def _gaps(ref_logits, served, pick):
    best = jnp.max(ref_logits, -1)
    at = lambda t: jnp.take_along_axis(ref_logits, t[:, None], -1)[:, 0]  # noqa: E731
    return best - at(served), best - at(pick)


def readings(ref_mod, config: Dict, key, storage, samples: List,
             control: bool = False) -> Dict[str, float]:
    """{"logit_gap": widest gap of a served token, "logit_gap_mean": their
    mean, "tokens": compared [, "control_gap", "control_gap_mean": the
    same of the tokens the float8 reference puts first]}."""
    if not samples:
        return {"logit_gap": NOTHING, "logit_gap_mean": NOTHING, "tokens": 0}
    tokens, rows, served = _batch(samples)
    ref = ref_mod.Reference(config, key, storage)
    h = ref.hidden(tokens, rows)
    hc = None
    if control:
        ctl = ref_mod.Reference(config, key, storage, fp8=True)
        hc = ctl.hidden(tokens, rows)
    gap = cgap = tot = ctot = 0.0
    for c in range(0, len(rows), ROW_CHUNK):
        r_log = ref.logits(h[c:c + ROW_CHUNK])
        srv = jnp.asarray(served[c:c + ROW_CHUNK])
        pick = (jnp.argmax(ctl.logits(hc[c:c + ROW_CHUNK]), -1)
                if control else srv)
        g, gc = _gaps(r_log, srv, pick.astype(jnp.int32))
        gap = max(gap, float(jnp.max(g)))
        cgap = max(cgap, float(jnp.max(gc)))
        tot += float(jnp.sum(g))
        ctot += float(jnp.sum(gc))
    out = {"logit_gap": gap, "logit_gap_mean": tot / len(rows),
           "tokens": int(len(rows))}
    if control:
        out.update(control_gap=cgap, control_gap_mean=ctot / len(rows))
    return out


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Dict[str, Dict[str, float]]:
    """Each compared number beside its limit (an upper bound)."""
    return {k: {"value": values[k], "limit": limits[k]} for k in limits}


def passed(numbers: Dict[str, Dict[str, float]]) -> bool:
    return all(n["value"] <= n["limit"] for n in numbers.values())


def short_answers(samples: List) -> int:
    """Sampled finished requests that did not emit exactly what they asked
    for."""
    return sum(1 for r in samples
               if r.done_t is not None and len(r.tokens) != r.max_new)


def free(*trees) -> None:
    for t in trees:
        for leaf in jax.tree.leaves(t):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                leaf.delete()


def peak_bytes(device) -> Optional[int]:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")
