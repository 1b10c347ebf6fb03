"""Profile the verify kernel's components on the real chip."""
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from tendermint_tpu.ops import verify as V
from tendermint_tpu.ops import curve as C
from tendermint_tpu.ops import field as F
from tendermint_tpu.crypto import ed25519_ref as ref

B = int(os.environ.get("B", "8192"))

sk = ref.gen_privkey(b"\x42" * 32)
pk = sk[32:]
msgs = [b"profile-%d" % i for i in range(B)]
sigs = [ref.sign(sk, m) for m in msgs]

t0 = time.perf_counter()
a, r, s, k, pre = V.prepare_batch([pk] * B, msgs, sigs)
print(f"host prepare_batch           {(time.perf_counter()-t0)*1e3:9.2f} ms")
a, r, s, k = (jnp.asarray(x) for x in (a, r, s, k))
aT, rT, sT, kT = (x.T for x in (a, r, s, k))


def timeit(name, fn, *args, iters=3):
    out = fn(*args)
    _ = np.asarray(jax.tree_util.tree_leaves(out)[0])
    t0 = time.perf_counter()
    for _i in range(iters):
        out = fn(*args)
        _ = np.asarray(jax.tree_util.tree_leaves(out)[0])
    dt = (time.perf_counter() - t0) / iters
    print(f"{name:28s} {dt*1e3:9.2f} ms   {B/dt:12.1f} /s")
    return out


decomp = jax.jit(lambda e: C.decompress(e, zip215=True))
a_pt, _ = decomp(aT)
a_neg = jax.jit(C.point_neg)(a_pt)
straus = jax.jit(C.double_scalar_mul_base)
femul = jax.jit(lambda u, v: jax.lax.fori_loop(0, 1000, lambda i, w: F.fe_mul(w, v), u))
fesq = jax.jit(lambda u: jax.lax.fori_loop(0, 1000, lambda i, w: F.fe_square(w), u))
pdbl = jax.jit(lambda p: jax.lax.fori_loop(0, 100, lambda i, w: C.point_double(w, out_t=False), p))
padd = jax.jit(lambda p, q: jax.lax.fori_loop(0, 100, lambda i, w: C.point_add(w, q, out_t=True), p))

timeit("full verify_kernel", V.verify_kernel, a, r, s, k)
timeit("decompress (B)", decomp, aT)
timeit("straus double_scalar", straus, sT, kT, a_neg)
x = a_pt[1]
timeit("fe_mul x1000", femul, x, x)
timeit("fe_square x1000", fesq, x)
timeit("point_double(noT) x100", pdbl, a_pt)
timeit("point_add(T) x100", padd, a_pt, a_neg)
