"""The default dropout mask's statistics and BERT's gather-first decode."""
import jax
import numpy as np

from mxnet_tpu.ops import nn as opsnn


def test_hash_dropout_mask_statistics():
    k = jax.random.PRNGKey(42)
    for keep in (0.5, 0.7, 0.9):
        m = np.asarray(opsnn._hash_keep_mask(k, (64, 128, 768), keep))
        assert abs(m.mean() - keep) < 2e-3
        flat = m.reshape(-1).astype(np.float64)
        corr = np.corrcoef(flat[:-1], flat[1:])[0, 1]
        assert abs(corr) < 3e-3
    # distinct keys decorrelate
    m1 = np.asarray(opsnn._hash_keep_mask(jax.random.PRNGKey(1), (4096,), .5))
    m2 = np.asarray(opsnn._hash_keep_mask(jax.random.PRNGKey(2), (4096,), .5))
    assert 0.4 < (m1 == m2).mean() < 0.6


def test_bert_gather_first_mlm_matches_full_decode():
    """Gather-first decode must produce exactly the logits the full-seq
    path gathers afterwards."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.bert import bert_tiny
    np.random.seed(0)
    mx.random.seed(0)
    net = bert_tiny(vocab_size=50, max_length=16)
    net.initialize(mx.init.Xavier())
    B, T, M = 2, 16, 4
    tokens = mx.nd.array(np.random.randint(4, 50, (B, T)).astype("float32"))
    segments = mx.nd.zeros((B, T))
    pos = np.stack([np.random.choice(T, M, replace=False)
                    for _ in range(B)]).astype("float32")
    positions = mx.nd.array(pos)
    _, _, full, _ = net(tokens, segments, None)
    _, _, picked, _ = net(tokens, segments, None, positions)
    want = np.take_along_axis(full.asnumpy(),
                              pos.astype(int)[:, :, None], axis=1)
    np.testing.assert_allclose(picked.asnumpy(), want, rtol=1e-5, atol=1e-5)
