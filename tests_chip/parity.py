"""The comparison the chip tests share."""

import numpy as np


def assert_close(got, want, tol):
    """``|got - want| <= tol * (|want| + rms(want))`` elementwise: a
    relative bound, with an absolute floor that follows the reference's
    own magnitude (a dW that sums a million products, a softmax output
    that shrinks with depth)."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    rms = float(np.sqrt(np.mean(np.square(want))))
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * rms)
