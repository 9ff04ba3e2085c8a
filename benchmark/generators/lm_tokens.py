"""A tokenized corpus for causal-LM training, drawn vectorised from
``numpy.random.default_rng(seed)``: one seed, one data set.

One stream of token ids, Zipf(``zipf``) over the configuration's
vocabulary (rank r has probability proportional to ``r ** -zipf``; id =
rank - 1), cut into documents of log-normal length (median ``doc_median``
tokens, sigma 1) whose last token is the end-of-text id, documents
concatenated with no mask across them (OLMo's practice), then cut into
rows of ``seq_len``. ``marker`` says whether a document ends inside the
row; every ``invalid_every``-th row is flagged invalid for the ETL filter
to drop, so ``rows`` valid rows come out of ``rows * k / (k - 1)`` raw
ones (the columns ``etl_select`` expects, as ``glue_tokens``)."""
import numpy as np

EOS = 50279   # <|endoftext|> of the GPT-NeoX tokenizer OLMoE uses


def generate(seed: int, sizes: dict, *, rows: int, seq_len: int,
             invalid_every: int = 5, zipf: float = 1.0,
             doc_median: int = 600) -> dict:
    vocab = sizes["vocab_size"]
    eos = min(EOS, vocab - 1)
    n_raw = rows * invalid_every // (invalid_every - 1)
    n_tokens = n_raw * seq_len
    rng = np.random.default_rng(seed)
    cdf = np.cumsum(np.arange(1, vocab + 1, dtype=np.float64) ** -zipf)
    stream = np.searchsorted(cdf, rng.random(n_tokens) * cdf[-1])
    stream = np.minimum(stream, vocab - 1).astype(np.int64)
    lengths = np.maximum(1, rng.lognormal(
        np.log(doc_median), 1.0, size=2 + 4 * n_tokens // doc_median
    ).astype(np.int64))
    ends = np.cumsum(lengths) - 1
    stream[ends[ends < n_tokens]] = eos
    ids = stream.reshape(n_raw, seq_len)
    cols = {f"t{i}": ids[:, i] for i in range(seq_len)}
    cols["marker"] = (ids == eos).any(axis=1).astype(np.int64)
    cols["valid"] = (
        np.arange(n_raw) % invalid_every != invalid_every - 1
    ).astype(np.int64)
    return cols
