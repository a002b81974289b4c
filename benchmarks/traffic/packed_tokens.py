"""Packed token batches from a seed: documents with log-normal lengths,
ids Zipf over the vocabulary (ranks scattered by a seeded permutation),
joined with an EOS separator and cut into sequences of ``seq_len`` tokens.
Every seed draws the same number of sequences of the same length, so the
work is the same from seed to seed and only the rows differ.
"""

import numpy as np


def make(params, cfg, seed, global_batch):
    """``pool`` batches of ``(tokens, targets)``, int32 ``[global_batch,
    seq_len]`` each; targets are the tokens shifted by one."""
    rng = np.random.default_rng(seed)
    vocab, seq = cfg["vocab_size"], params["seq_len"]
    eos = cfg.get("eos_token_id", vocab - 1)
    need = params["pool"] * global_batch * (seq + 1)
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks ** -params["zipf_exponent"]
    ids = rng.permutation(vocab)[
        rng.choice(vocab, size=need, p=p / p.sum())].astype(np.int32)
    lo, hi = params["doc_len_clip"]
    mean_len = params["doc_len_median"]
    n_docs = int(need / lo) + 1
    lens = np.clip(rng.lognormal(np.log(mean_len), params["doc_len_sigma"],
                                 n_docs), lo, hi).astype(np.int64)
    ends = np.cumsum(lens + 1) - 1       # one EOS closes each document
    ids[ends[ends < need]] = eos
    rows = ids.reshape(params["pool"], global_batch, seq + 1)
    return [(np.ascontiguousarray(r[:, :-1]), np.ascontiguousarray(r[:, 1:]))
            for r in rows]
