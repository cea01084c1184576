"""The float32 exactness contract, stated as a tolerance.

A served forward and its reference are the same float32 computation, but
XLA compiles them at different shapes (a batched, bucket-padded executor
against an unbatched jitted forward; a sampled subgraph against the full
graph) and may round the two programs differently by about one ULP.  The
contract is therefore a few float32 ULP of the output's scale, not bitwise
equality.  Integer paths, cache and sampler structure stay exact.
"""

import numpy as np

F32_EPS = float(np.finfo(np.float32).eps)


def assert_ulp_close(got, ref, ulps: int = 4, err_msg: str = "") -> None:
    """|got - ref| <= ulps * eps * (|ref| + max|ref|), elementwise."""
    ref = np.asarray(ref)
    scale = float(np.max(np.abs(ref))) if ref.size else 0.0
    np.testing.assert_allclose(got, ref, rtol=ulps * F32_EPS,
                               atol=ulps * F32_EPS * scale, err_msg=err_msg)
