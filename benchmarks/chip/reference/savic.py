"""Plain reference of SAVIC rounds (Algorithm 1 of "Local Methods
with Adaptivity via Scaling") with Adam-type global scaling.

A round: each of M clients starts from the server point x and momentum m,
and takes H local steps on its own microbatches,

    g = grad f(x_c; batch[c, h]);  m_c <- beta1 m_c + g;
    x_c <- x_c - gamma m_c / max(alpha, sqrt(D2)),

with the global D2 fixed for the round. Then the server averages x_c and
m_c over the clients, and updates D2 from the client-averaged gradient of
the last local step: D2 <- b_t D2 + (1 - b_t) g_avg**2, with Adam's
debiased b_t = (b - b**(t+1)) / (1 - b**(t+1)) at the t-th update (so the
first update sets D2 = g_avg**2). D2 starts at 1 and m at 0.

All arithmetic is in the parameters' dtype (float32 for the check). On one
device the clients are computed one after another and the averages leaf by
leaf. On several, one client a device (as the program's four-chip cells
run), each client is computed on its own device and each average leaf by
leaf on every device from all the clients' copies of the leaf, summed in
client order as on one device, so every device holds the server's state
and no tree goes through the host.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class SavicReference:
    """``run(params, batch_at, rounds, read_grad)`` -> (round losses,
    ``read_grad`` of the first round's client-averaged gradient, the
    server's parameters after the rounds).

    ``loss(params, tokens, labels)`` is the plain model's loss;
    ``batch_at(r)`` gives round r's numpy tokens and labels, each
    (M, H, b, S). ``params`` is consumed (its buffers are donated); on
    several devices it is best made on all of them (``self.everywhere``).
    """

    def __init__(self, loss, *, gamma, beta1, alpha, beta2, devices):
        self.hp = dict(gamma=gamma, beta1=beta1, alpha=alpha)
        self.beta2 = beta2
        self.devices = list(devices)

        def step(x, m, d2, tokens, labels):
            value, g = jax.value_and_grad(loss)(x, tokens, labels)
            m = jax.tree.map(lambda mi, gi: beta1 * mi + gi, m, g)
            x = jax.tree.map(
                lambda xi, mi, di: xi - gamma * mi / jnp.maximum(
                    alpha, jnp.sqrt(di)), x, m, d2)
            return x, m, g, value

        self._step = jax.jit(step)
        self._step_donating = jax.jit(step, donate_argnums=(0, 1))
        self._add = jax.jit(lambda a, b: a + b, donate_argnums=0)
        self._scale = jax.jit(lambda a, s: (a * s).astype(a.dtype),
                              donate_argnums=0)
        self._d2 = jax.jit(
            lambda d2, gsum, beta, inv_m: (beta * d2 + (1.0 - beta)
                                           * jnp.square(gsum * inv_m)
                                           ).astype(d2.dtype),
            donate_argnums=0)
        if len(self.devices) > 1:
            mesh = Mesh(np.array(self.devices), ("clients",))
            self.everywhere = NamedSharding(mesh, P())
            self._one_a_device = NamedSharding(mesh, P("clients"))
            self._lead = jax.jit(lambda a: a[None], donate_argnums=0)

            def client_sum(a, scale):
                acc = a[0]
                for c in range(1, a.shape[0]):
                    acc = acc + a[c]
                return acc if scale is None else \
                    (acc * scale).astype(acc.dtype)

            self._client_sum = jax.jit(client_sum,
                                       out_shardings=self.everywhere)
            self._fill = jax.jit(
                lambda t, v: jax.tree.map(lambda a: jnp.full_like(a, v), t),
                static_argnums=1, out_shardings=self.everywhere)

    def _mean_leaves(self, trees):
        """Client average, leaf by leaf on devices[0]; frees the inputs."""
        dev0 = self.devices[0]
        leaves = [jax.tree.leaves(t) for t in trees]
        treedef = jax.tree.structure(trees[0])
        trees.clear()
        M = len(leaves)
        out = []
        for i in range(len(leaves[0])):
            acc = jax.device_put(leaves[0][i], dev0)
            leaves[0][i] = None
            for c in range(1, M):
                acc = self._add(acc, jax.device_put(leaves[c][i], dev0))
                leaves[c][i] = None
            out.append(self._scale(acc, np.float32(1.0 / M)))
        return jax.tree.unflatten(treedef, out)

    def run(self, params, batch_at, rounds: int, read_grad):
        if len(self.devices) > 1:
            return self._run_one_a_device(params, batch_at, rounds,
                                          read_grad)
        dev0 = self.devices[0]
        x = jax.device_put(params, dev0)
        del params
        m = jax.tree.map(jnp.zeros_like, x)
        d2 = jax.tree.map(jnp.ones_like, x)
        round_losses, g_read = [], None
        for r in range(rounds):
            tokens, labels = batch_at(r)
            M, H = tokens.shape[:2]
            dev = [self.devices[c % len(self.devices)] for c in range(M)]
            last_user = {d: max(c for c in range(M) if dev[c] == d)
                         for d in dev}
            xs = [jax.device_put(x, dev[c]) for c in range(M)]
            ms = [jax.device_put(m, dev[c]) for c in range(M)]
            d2s = {d: jax.device_put(d2, d) for d in set(dev)}
            del x, m
            losses, g_sum = [], None
            for h in range(H):
                for c in range(M):
                    # the clients on one device share the round's start
                    # point until the last of them takes its first step
                    fn = self._step_donating \
                        if h > 0 or c == last_user[dev[c]] else self._step
                    xs[c], ms[c], g, value = fn(
                        xs[c], ms[c], d2s[dev[c]],
                        jax.device_put(tokens[c, h], dev[c]),
                        jax.device_put(labels[c, h], dev[c]))
                    losses.append(value)
                    if h == H - 1:
                        g = jax.device_put(g, dev0)
                        g_sum = g if g_sum is None else \
                            jax.tree.map(self._add, g_sum, g)
                    del g
            round_losses.append(float(np.mean(
                [float(v) for v in jax.device_get(losses)])))
            x = self._mean_leaves(xs)
            m = self._mean_leaves(ms)
            d2 = d2s[dev0] if dev0 in d2s else jax.device_put(d2, dev0)
            del d2s
            b = self.beta2
            beta = np.float32((b - b ** (r + 1)) / (1.0 - b ** (r + 1)))
            if r == 0:
                g_read = read_grad(jax.tree.map(
                    lambda a: a * np.float32(1.0 / M), g_sum))
            d2 = jax.tree.map(
                lambda di, gi: self._d2(di, gi, beta, np.float32(1.0 / M)),
                d2, g_sum)
            del g_sum
        return round_losses, g_read, x


    def _across(self, trees, scale=None):
        """Leaf by leaf, the clients' sum in client order (times ``scale``)
        on every device, from ``trees``, client c's tree on device c;
        frees the inputs."""
        leaves = [jax.tree.leaves(t) for t in trees]
        treedef = jax.tree.structure(trees[0])
        trees.clear()
        out = []
        for i in range(len(leaves[0])):
            parts = [self._lead(client[i]) for client in leaves]
            for client in leaves:
                client[i] = None
            stacked = jax.make_array_from_single_device_arrays(
                (len(parts),) + parts[0].shape[1:], self._one_a_device,
                parts)
            del parts
            out.append(self._client_sum(stacked, scale))
            del stacked
        return jax.tree.unflatten(treedef, out)

    def _run_one_a_device(self, params, batch_at, rounds, read_grad):
        devs = self.devices
        x = jax.device_put(params, self.everywhere)
        del params
        m = self._fill(x, 0.0)
        d2 = self._fill(x, 1.0)

        def mine(tree, c):
            """Device c's copy of a tree held on every device."""
            return jax.tree.map(lambda a: {s.device: s.data for s in
                                           a.addressable_shards}[devs[c]],
                                tree)

        round_losses, g_read = [], None
        for r in range(rounds):
            tokens, labels = batch_at(r)
            M, H = tokens.shape[:2]
            if M != len(devs):
                raise ValueError(f"{M} clients on {len(devs)} devices: on "
                                 f"several devices the reference runs one "
                                 f"client a device")
            xs = [mine(x, c) for c in range(M)]
            ms = [mine(m, c) for c in range(M)]
            d2s = [mine(d2, c) for c in range(M)]
            del x, m
            losses, gs = [], [None] * M
            for h in range(H):
                for c in range(M):
                    xs[c], ms[c], g, value = self._step_donating(
                        xs[c], ms[c], d2s[c],
                        jax.device_put(tokens[c, h], devs[c]),
                        jax.device_put(labels[c, h], devs[c]))
                    losses.append(value)
                    if h == H - 1:
                        gs[c] = g
                    del g
            del d2s
            round_losses.append(float(np.mean(
                [float(v) for v in jax.device_get(losses)])))
            inv_m = np.float32(1.0 / M)
            x = self._across(xs, inv_m)
            m = self._across(ms, inv_m)
            g_sum = self._across(gs)
            b = self.beta2
            beta = np.float32((b - b ** (r + 1)) / (1.0 - b ** (r + 1)))
            if r == 0:
                g_read = read_grad(jax.tree.map(lambda a: a * inv_m, g_sum))
            d2 = jax.tree.map(lambda di, gi: self._d2(di, gi, beta, inv_m),
                              d2, g_sum)
            del g_sum
        return round_losses, g_read, x
