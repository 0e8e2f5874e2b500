"""Operations and HBM bytes of one ``hessian_accum`` call, H = X^T X.

The least the call needs: x (n, d) read once and H (d, d) f32 written
once; 2 n d^2 operations (the kernel computes the whole square, not one
triangle). On one v5e the calls of the quantize cell run faster than a
count of every tile's re-fetch of x allows, so the kernel's pipeline does
not re-read x per output tile, and only this least count bounds it.
"""


def count(n: int, d: int, x_bytes: int = 4):
    return 2.0 * n * d * d, float(x_bytes * n * d + 4 * d * d)
