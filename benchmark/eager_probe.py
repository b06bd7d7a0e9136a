"""Instrument the eager dispatch path on the real chip (round-4 diagnosis).

Breaks down where time goes in eager exp().backward() and eager Convolution
forward, steady-state, with value-fetched timing windows.
"""
import time
import sys

import jax
import jax.numpy as jnp

import os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import mxnet_tpu as mx
from mxnet_tpu import autograd, cache
from mxnet_tpu.ndarray import ndarray as ndmod

cache.enable_compile_cache()


def fetch(nd_or_jax):
    a = nd_or_jax.data if hasattr(nd_or_jax, "data") else nd_or_jax
    return float(a.ravel()[0])


def timeit(label, f, n=10, warmup=3):
    for _ in range(warmup):
        f()
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        f()
        ts.append((time.perf_counter() - t0) * 1e3)
    ts.sort()
    print(f"{label:45s} med={ts[len(ts)//2]:8.2f} ms  min={ts[0]:8.2f}  max={ts[-1]:8.2f}")
    return ts[len(ts) // 2]


print("devices:", jax.devices())

# --- 1. eager exp forward ---
x = mx.nd.ones((1024, 1024))
x.attach_grad()
timeit("exp fwd (fetched)", lambda: fetch(mx.nd.exp(x)))

# --- 2. eager exp backward, whole ---
def bwd():
    with autograd.record():
        y = mx.nd.exp(x)
    y.backward()
    return fetch(x.grad)

timeit("exp fwd+bwd (fetched)", bwd)

# --- 3. instrument the pieces of backward ---
import mxnet_tpu.autograd as ag

_orig_node_vjp = ag._node_vjp
_orig_write_grad = ag._write_grad
acc = {}

def timed_node_vjp(node, cots):
    t0 = time.perf_counter()
    r = _orig_node_vjp(node, cots)
    acc["node_vjp"] = acc.get("node_vjp", 0) + (time.perf_counter() - t0)
    return r

def timed_write_grad(x_, v):
    t0 = time.perf_counter()
    r = _orig_write_grad(x_, v)
    acc["write_grad"] = acc.get("write_grad", 0) + (time.perf_counter() - t0)
    return r

ag._node_vjp = timed_node_vjp
ag._write_grad = timed_write_grad

for _ in range(3):
    bwd()
acc.clear()
N = 5
t0 = time.perf_counter()
for _ in range(N):
    bwd()
tot = (time.perf_counter() - t0) / N * 1e3
print(f"backward breakdown over {N} calls: total {tot:.2f} ms/call")
for k, v in acc.items():
    print(f"  {k:20s} {v / N * 1e3:8.2f} ms/call")
ag._node_vjp = _orig_node_vjp
ag._write_grad = _orig_write_grad

# --- 3b. inside _node_vjp: is it the vjp_exec call itself? ---
from mxnet_tpu.ops import registry as reg
with autograd.record():
    y = mx.nd.exp(x)
node = y._tape_node
key_probe = {}

# replicate the cache lookup by calling _node_vjp once then timing vjp_exec directly
cot = jnp.ones(y.shape, y.data.dtype)
ag._node_vjp(node, [cot])  # populate cache
print("VJP cache size:", len(ag._VJP_CACHE))
vjp_exec = next(iter(ag._VJP_CACHE.values()))
jx = (x.data,)

def raw_vjp():
    out = vjp_exec(jx, (cot,))
    return float(out[0].ravel()[0])

timeit("raw cached vjp_exec (fetched)", raw_vjp)
autograd._STATE.tape = []

# --- 4. eager Convolution forward ---
data = mx.nd.random.uniform(shape=(32, 64, 56, 56))
w = mx.nd.random.uniform(shape=(64, 64, 3, 3))
b = mx.nd.zeros((64,))

def conv():
    out = mx.nd.Convolution(data, w, b, kernel=(3, 3), num_filter=64, pad=(1, 1))
    return fetch(out)

timeit("eager Convolution fwd (fetched)", conv)

# what does the raw jitted conv cost?
convop = reg.get_op("Convolution")
attrs = dict(kernel=(3, 3), num_filter=64, pad=(1, 1))
ex = reg._executor(convop, attrs)

def rawconv():
    return float(ex(data.data, w.data, b.data).ravel()[0])

timeit("raw cached jitted conv (fetched)", rawconv)
print("JIT cache size:", len(reg._JIT_CACHE))

# --- 5. tiny jitted op round trip for reference ---
tiny = jax.jit(lambda a: a + 1)
ta = jnp.ones((8, 8))
timeit("tiny jit roundtrip (fetched)", lambda: float(tiny(ta).ravel()[0]))

# --- 6. plain jnp dispatch (no mx wrapper) ---
timeit("plain jnp.exp (fetched)", lambda: float(jnp.exp(x.data).ravel()[0]))
