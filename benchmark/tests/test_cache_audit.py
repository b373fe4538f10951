"""The cache audit's two statistics on rows whose precision is known:
bf16 rounding reads well under the limit, an 8-bit row with a scale of
its own well over it (``docs_measure.LATENT_ROW_REL_ERR``)."""
import ml_dtypes
import numpy as np

import cache_audit_kimi_k2 as audit
import docs_measure


def rows(seed: int = 0) -> np.ndarray:
    """Normalised latents as layer 0 makes them: unit rms, 576 wide."""
    return np.random.default_rng(seed).standard_normal((4096, 576)).astype(np.float32)


def test_rel_err_is_rms_over_rms():
    want = rows()
    assert audit.rel_err(want, want) == 0.0
    assert abs(audit.rel_err(want * 1.01, want) - 0.01) < 1e-6
    assert abs(audit.rel_err(want + 0.5, want) - 0.5 / np.sqrt(np.mean(want ** 2))) < 1e-3


def test_bf16_rows_pass_and_8_bit_rows_fail_the_limit():
    want = rows(1)
    held = want.astype(ml_dtypes.bfloat16).astype(np.float32)
    as_held = audit.rel_err(held, want)
    through_8 = audit.rel_err(audit.through_8_bits(held), want)
    # one bf16 rounding: 2^-9 at most, about 2^-9 / sqrt(3) * 0.72 in rms
    assert 0.001 < as_held < 0.002
    # an 8-bit step is max|row| / 127 with max about 3.3 sigma of 576 values:
    # 3.3 / 127 / sqrt(12) = 0.0075 of the rms
    assert 0.006 < through_8 < 0.009
    assert 2 * as_held < docs_measure.LATENT_ROW_REL_ERR < through_8 / 1.4


def test_through_8_bits_keeps_at_most_255_levels_a_row():
    held = audit.through_8_bits(rows(2)[:8])
    assert all(len(np.unique(r)) <= 255 for r in held)
    assert held.shape == (8, 576)
