"""Where do eager arrays live, and which dispatch path is slow?"""
import time, sys
import jax, jax.numpy as jnp
import os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import mxnet_tpu as mx
from mxnet_tpu import cache

cache.enable_compile_cache()

def timeit(label, f, n=8, warmup=3):
    for _ in range(warmup): f()
    ts = []
    for _ in range(n):
        t0 = time.perf_counter(); f(); ts.append((time.perf_counter()-t0)*1e3)
    ts.sort()
    print(f"{label:52s} med={ts[len(ts)//2]:8.2f} ms min={ts[0]:8.2f}")

x = mx.nd.ones((1024, 1024))
print("default ctx:", mx.current_context())
print("x.data devices:", x.data.devices(), "committed:", x.data.committed)

tpu = jax.devices()[0]
cpu = jax.devices("cpu")[0]
xt = jax.device_put(jnp.ones((1024, 1024)), tpu)
xc = jax.device_put(jnp.ones((1024, 1024)), cpu)

timeit("eager jnp.exp on TPU-committed", lambda: float(jnp.exp(xt).ravel()[0]))
timeit("eager jnp.exp on CPU-committed", lambda: float(jnp.exp(xc).ravel()[0]))

jexp = jax.jit(jnp.exp)
jexp(xt); jexp(xc)
timeit("jit jnp.exp on TPU-committed", lambda: float(jexp(xt).ravel()[0]))
timeit("jit jnp.exp on CPU-committed", lambda: float(jexp(xc).ravel()[0]))

# is it the execute or the fetch? time without fetch but with a later sync
def nofetch():
    ys = [jexp(xt) for _ in range(10)]
    return float(ys[-1].ravel()[0])
timeit("jit exp x10 on TPU, single fetch", nofetch, n=4, warmup=1)

# donate / no ravel: fetch via np.asarray of a 1-elem slice
y = jexp(xt)
timeit("fetch only: float(y.ravel()[0]) again", lambda: float(y.ravel()[0]))
timeit("fetch only: float(y[0,0])", lambda: float(y[0, 0]))
