"""GANModule — the adversarial G/D training step as ONE fused XLA program.

Reference: ``example/gan/dcgan.py`` drives two Modules imperatively — per
batch it dispatches G forward, two D forward+backwards (fake/0, real/1), the
D update, a third D forward+backward (fake/1) for input gradients, the G
backward through those, and the G update: ~8 engine round trips plus two
host-side numpy uploads (latents, labels) per batch.

TPU mapping: the whole alternating step is one donated jitted program built
from the two executors' shared gradient cores (``Executor._make_grad_core``,
so loss construction and head-grad conventions cannot diverge from the
imperative path):

* latents are drawn **in-graph** from ``jax.random`` (no per-batch host
  upload; a ``latents=`` override feeds recorded noise for parity tests),
* the D update consumes the fake(0)+real(1) **summed** parameter gradients,
  exactly like the reference's explicit grad accumulation,
* G updates through the **updated** D's input gradients at label=1 (the
  reference ordering), with the gradient core re-deriving G's forward under
  the same rng so the fake image and its VJP agree,
* parameters, optimizer state, BatchNorm statistics and the rng counter all
  advance on-device across a K-step ``lax.scan`` window — K train steps cost
  one host dispatch, and ``WindowBoundary`` gives pipelined callers their
  backpressure fence (same contract as ``Module.train_window``).

D's discriminator outputs from the real pass (pre-update, matching the
reference's metric read) are published at the window boundary.
"""

from __future__ import annotations

import logging

import numpy as np

from .. import telemetry as _tm
from ..base import MXNetError
from ..executor import _fold_rng
from ..initializer import Normal
from ..io import DataBatch
from ..ndarray import NDArray
from .executor_group import _map_state, _optimizer_token
from .module import Module, WindowBoundary


def _as_jax(x):
    import jax.numpy as jnp

    return x._data if isinstance(x, NDArray) else jnp.asarray(x)


class GANModule:
    """Two adversarially-trained Modules behind one fused train step.

    Parameters
    ----------
    generator : Symbol
        Maps latent ``rand_name`` (n, code, 1, 1) to an image.
    discriminator : Symbol
        Loss-headed real/fake classifier over ``data_name``/``label_name``.
    context : Context
    batch_size : int
    code_shape : tuple
        Per-sample latent shape, e.g. ``(100, 1, 1)``.
    data_shape : tuple
        Per-sample image shape, e.g. ``(3, 64, 64)``.
    """

    def __init__(self, generator, discriminator, context=None, batch_size=64,
                 code_shape=(100, 1, 1), data_shape=(3, 64, 64),
                 rand_name="rand", data_name="data", label_name="label",
                 logger=logging):
        self._rand_name = rand_name
        self._data_name = data_name
        self._label_name = label_name
        self.batch_size = batch_size
        self.code_shape = tuple(code_shape)
        self.data_shape = tuple(data_shape)
        self.logger = logger
        self.mod_g = Module(generator, data_names=(rand_name,),
                            label_names=None, logger=logger, context=context)
        self.mod_d = Module(discriminator, data_names=(data_name,),
                            label_names=(label_name,), logger=logger,
                            context=context)
        self._plans = {}
        self._step = 0

    # ------------------------------------------------------------------
    def bind(self):
        bs = self.batch_size
        self.mod_g.bind(data_shapes=[(self._rand_name,
                                      (bs,) + self.code_shape)])
        # inputs_need_grad: G trains through D's gradient wrt its image input
        self.mod_d.bind(data_shapes=[(self._data_name,
                                      (bs,) + self.data_shape)],
                        label_shapes=[(self._label_name, (bs,))],
                        inputs_need_grad=True)
        return self

    def init_params(self, initializer=None, force_init=False):
        initializer = initializer or Normal(0.02)
        self.mod_g.init_params(initializer=initializer, force_init=force_init)
        self.mod_d.init_params(initializer=initializer, force_init=force_init)
        return self

    def init_optimizer(self, optimizer="adam",
                       optimizer_params=(("learning_rate", 0.0002),
                                         ("beta1", 0.5)),
                       force_init=False):
        self.mod_g.init_optimizer(optimizer=optimizer,
                                  optimizer_params=optimizer_params,
                                  force_init=force_init)
        self.mod_d.init_optimizer(optimizer=optimizer,
                                  optimizer_params=optimizer_params,
                                  force_init=force_init)
        return self

    # ------------------------------------------------------------------
    def _fusable(self):
        g, d = self.mod_g, self.mod_d
        return (
            getattr(g._optimizer, "jax_apply", None) is not None
            and getattr(d._optimizer, "jax_apply", None) is not None
            and not g._update_on_kvstore and not d._update_on_kvstore
            and g._exec_group._exec._monitor_callback is None
            and d._exec_group._exec._monitor_callback is None
            and not g._exec_group._exec._naive
            and not d._exec_group._exec._naive
        )

    def _opt_host(self, mod):
        """Mirror of ``ExecutorGroup.update_fused``'s one-time structure
        build: updatable param names, their optimizer-state NDArray leaves
        and the flatten treedef (shared state objects, so checkpointing via
        the modules stays coherent)."""
        import jax

        exe = mod._exec_group._exec
        optimizer, updater = mod._optimizer, mod._updater
        keys, names, nd_states = [], [], []
        for i, n in enumerate(mod._exec_group.param_names):
            if n not in exe.arg_dict or exe.grad_req.get(n, "null") == "null":
                continue
            w = exe.arg_dict[n]
            if i not in updater.states:
                st = optimizer.create_state(i, w)
                st = _map_state(
                    st,
                    lambda nd: NDArray(
                        jax.device_put(nd._data, w._data.sharding)
                    ),
                )
                updater.states[i] = st
            keys.append(i)
            names.append(n)
            nd_states.append(updater.states[i])
        nd_leaves, state_td = jax.tree_util.tree_flatten(
            [_map_state(st, lambda nd: nd) for st in nd_states],
            is_leaf=lambda x: isinstance(x, NDArray),
        )
        return {"keys": keys, "names": names, "nd_leaves": nd_leaves,
                "state_td": state_td}

    def _advance_counts(self, mod, host, n_steps):
        """Host-side lr/wd/t mirror (same convention as ``update_fused``):
        the program advances t on-device each iteration, lr/wd stay frozen
        for the window; the host count lands on the window-end value."""
        optimizer = mod._optimizer
        for i in host["keys"]:
            optimizer._update_count(i)
        lrs = [optimizer._get_lr(i) for i in host["keys"]]
        wds = [optimizer._get_wd(i) for i in host["keys"]]
        t0 = max(optimizer._index_update_count[i] for i in host["keys"])
        for _ in range(n_steps - 1):
            for i in host["keys"]:
                optimizer._update_count(i)
        return lrs, wds, t0

    # ------------------------------------------------------------------
    def _build_plan(self, n_steps, with_latents):
        import jax
        import jax.numpy as jnp

        g_exe = self.mod_g._exec_group._exec
        d_exe = self.mod_d._exec_group._exec
        g_core = g_exe._make_grad_core()
        d_core = d_exe._make_grad_core()
        g_graph = g_exe.graph
        g_names = list(g_exe.arg_names)
        d_names = list(d_exe.arg_names)
        gi_rand = g_names.index(self._rand_name)
        di_data = d_names.index(self._data_name)
        di_label = d_names.index(self._label_name)

        g_host = self._opt_host(self.mod_g)
        d_host = self._opt_host(self.mod_d)
        g_idx = [g_names.index(n) for n in g_host["names"]]
        d_idx = [d_names.index(n) for n in d_host["names"]]
        g_opt, d_opt = self.mod_g._optimizer, self.mod_d._optimizer
        g_td, d_td = g_host["state_td"], d_host["state_td"]

        lab_dtype = d_exe.arg_dict[self._label_name].dtype
        bs = self.batch_size
        zeros_lab = jnp.zeros((bs,), lab_dtype)
        ones_lab = jnp.ones((bs,), lab_dtype)
        z_shape = (bs,) + self.code_shape
        z_dtype = g_exe.arg_dict[self._rand_name].dtype

        def apply_all(optimizer, args, idx, states_td, st_leaves, grads,
                      lrs, wds, t):
            new_args = list(args)
            states = jax.tree_util.tree_unflatten(states_td, st_leaves)
            new_states = []
            for k, i in enumerate(idx):
                w, st = args[i], states[k]
                nw, nst = optimizer.jax_apply(w, grads[k], st, lrs[k],
                                              wds[k], t, None)
                new_args[i] = nw
                new_states.append(nst)
            leaves, _ = jax.tree_util.tree_flatten(new_states)
            return new_args, leaves

        def step_fn(g_args, g_aux, d_args, d_aux, g_sts, d_sts,
                    g_key, d_key, step0, t_g, t_d,
                    g_lrs, g_wds, d_lrs, d_wds, real_stack, lat_stack):
            def body(carry, xs):
                (g_args, g_aux, d_args, d_aux, g_sts, d_sts,
                 sc, tg, td) = carry
                real_i, lat_i = xs
                g_fold = _fold_rng((g_key, sc))
                if with_latents:
                    z = lat_i.astype(z_dtype)
                else:
                    z = jax.random.normal(
                        jax.random.fold_in(g_fold, 0x6A77), z_shape, z_dtype
                    )

                # generate (reference: mod_g.forward(noise, is_train=True));
                # the G gradient core below re-derives this forward under
                # the SAME folded key, so XLA sees one generator pass
                g_full = list(g_args)
                g_full[gi_rand] = z
                g_outs, _ = g_graph.evaluate(g_full, list(g_aux), g_fold,
                                             True)
                fake = g_outs[0]

                sc3 = sc * np.uint32(3)
                # D on fake/0 then real/1, aux threading sequentially (the
                # reference's two is_train forwards); loss heads drive the
                # implicit backward (head_grads=None)
                d_fake = list(d_args)
                d_fake[di_data] = fake
                d_fake[di_label] = zeros_lab
                _outs_f, d_aux1, gm_f = d_core(
                    d_fake, list(d_aux), (d_key, sc3), None, {})
                d_real = list(d_args)
                d_real[di_data] = real_i
                d_real[di_label] = ones_lab
                outs_r, d_aux2, gm_r = d_core(
                    d_real, d_aux1, (d_key, sc3 + np.uint32(1)), None, {})

                # D update on SUMMED fake+real grads (reference accumulates
                # the fake-pass grads into the real-pass grads pre-update)
                d_grads = [gm_f[n] + gm_r[n] for n in d_host["names"]]
                new_d_args, new_d_sts = apply_all(
                    d_opt, d_args, d_idx, d_td, d_sts, d_grads,
                    d_lrs, d_wds, td)

                # G update through the UPDATED D's input gradient at
                # label=1 (reference ordering: d.update() precedes the
                # third pass)
                d_g = list(new_d_args)
                d_g[di_data] = fake
                d_g[di_label] = ones_lab
                _outs_f2, d_aux3, gm2 = d_core(
                    d_g, d_aux2, (d_key, sc3 + np.uint32(2)), None, {})
                head = gm2[self._data_name]
                # head grads are closure constants for the core's jax.grad,
                # so G differentiates sum(fake * head) treating head as
                # fixed — exactly mod_g.backward(diff_d)
                _g_outs, g_aux_new, gm_g = g_core(
                    g_full, list(g_aux), (g_key, sc), [head], {})
                g_grads = [gm_g[n] for n in g_host["names"]]
                new_g_args, new_g_sts = apply_all(
                    g_opt, g_args, g_idx, g_td, g_sts, g_grads,
                    g_lrs, g_wds, tg)

                one = np.uint32(1)
                carry = (new_g_args, g_aux_new, new_d_args, d_aux3,
                         new_g_sts, new_d_sts, sc + one, tg + 1, td + 1)
                return carry, tuple(outs_r)

            carry0 = (list(g_args), list(g_aux), list(d_args), list(d_aux),
                      list(g_sts), list(d_sts), step0, t_g, t_d)
            # XLA:CPU lowers convolutions inside a rolled while-loop body
            # through its generic path (~1.5x slower per step than the
            # imperative loop's standalone programs); unrolling restores
            # the fast thunks. TPU keeps the rolled scan — its conv
            # lowering is loop-invariant and compile time scales with the
            # unroll factor.
            unroll = n_steps if (
                jax.devices()[0].platform == "cpu" and n_steps <= 16) else 1
            carry, outs = jax.lax.scan(body, carry0,
                                       (real_stack, lat_stack),
                                       length=n_steps, unroll=unroll)
            (g_args, g_aux, d_args, d_aux, g_sts, d_sts, sc, _tg,
             _td) = carry
            last = tuple(o[-1] for o in outs)
            return (g_args, g_aux, d_args, d_aux, g_sts, d_sts, last)

        from ..executor import _compiler_options

        jit_fn = jax.jit(
            step_fn, donate_argnums=(0, 1, 2, 3, 4, 5),
            static_argnames=(),
            compiler_options=_compiler_options(),
        )
        return {"fn": jit_fn, "g_host": g_host, "d_host": d_host,
                "g_names": g_names, "d_names": d_names,
                "token": (_optimizer_token(g_opt), _optimizer_token(d_opt))}

    # ------------------------------------------------------------------
    def train_window(self, real_batch, n_steps=1, batches=None, latents=None):
        """Run ``n_steps`` fused G/D train steps as one program.

        ``real_batch`` alone trains every iteration on that batch;
        ``batches`` (list of real images or DataBatch, overrides
        ``n_steps``) trains iteration ``i`` on ``batches[i]``. ``latents``
        (per-step noise, stacked or listed) replaces the in-graph sampler —
        the parity-test hook. Returns a :class:`WindowBoundary` publishing
        the last iteration's real-pass D outputs (pre-update, the
        reference's metric read).
        """
        import jax
        import jax.numpy as jnp

        if batches is not None:
            if not batches:
                return None
            n_steps = len(batches)
        else:
            batches = [real_batch] * n_steps
        if not self._fusable():
            return self._serial_window(batches, latents)
        rows = [b.data[0] if isinstance(b, DataBatch) else b for b in batches]
        d_exe = self.mod_d._exec_group._exec
        g_exe = self.mod_g._exec_group._exec
        img_dtype = d_exe.arg_dict[self._data_name].dtype
        real_stack = jnp.stack([_as_jax(r) for r in rows]).astype(img_dtype)
        with_latents = latents is not None
        if with_latents:
            if isinstance(latents, (list, tuple)):
                lat_stack = jnp.stack([_as_jax(x) for x in latents])
            else:
                lat_stack = _as_jax(latents)
                if lat_stack.ndim == len(self.code_shape) + 1:
                    lat_stack = lat_stack[None]
            if lat_stack.shape[0] != n_steps:
                raise MXNetError(
                    f"latents: expected {n_steps} per-step draws, got "
                    f"{lat_stack.shape[0]}"
                )
        else:
            lat_stack = jnp.zeros((n_steps,), jnp.float32)  # scan filler

        key = (n_steps, with_latents)
        plan = self._plans.get(key)
        if plan is not None and plan["token"] != (
            _optimizer_token(self.mod_g._optimizer),
            _optimizer_token(self.mod_d._optimizer),
        ):
            plan = None
        if plan is None:
            _tm.counter("executor.fused_plan_compile").inc()
            plan = self._build_plan(n_steps, with_latents)
            self._plans[key] = plan
        else:
            _tm.counter("executor.fused_plan_hit").inc()
        _tm.counter("gan.window").inc()

        g_host, d_host = plan["g_host"], plan["d_host"]
        g_args = [g_exe.arg_dict[n]._data for n in plan["g_names"]]
        d_args = [d_exe.arg_dict[n]._data for n in plan["d_names"]]
        g_aux = [g_exe.aux_dict[n]._data for n in g_exe.aux_names]
        d_aux = [d_exe.aux_dict[n]._data for n in d_exe.aux_names]
        g_sts = [nd._data for nd in g_host["nd_leaves"]]
        d_sts = [nd._data for nd in d_host["nd_leaves"]]
        g_lrs, g_wds, t_g = self._advance_counts(self.mod_g, g_host, n_steps)
        d_lrs, d_wds, t_d = self._advance_counts(self.mod_d, d_host, n_steps)

        out = plan["fn"](
            g_args, g_aux, d_args, d_aux, g_sts, d_sts,
            g_exe._base_key, d_exe._base_key, np.uint32(self._step),
            np.int32(t_g), np.int32(t_d),
            g_lrs, g_wds, d_lrs, d_wds, real_stack, lat_stack,
        )
        (g_args_o, g_aux_o, d_args_o, d_aux_o, g_sts_o, d_sts_o, last) = out
        self._step += n_steps

        for n, leaf in zip(plan["g_names"], g_args_o):
            g_exe.arg_dict[n]._data = leaf
        for n, leaf in zip(plan["d_names"], d_args_o):
            d_exe.arg_dict[n]._data = leaf
        for n, leaf in zip(g_exe.aux_names, g_aux_o):
            g_exe.aux_dict[n]._data = leaf
        for n, leaf in zip(d_exe.aux_names, d_aux_o):
            d_exe.aux_dict[n]._data = leaf
        for nd, leaf in zip(g_host["nd_leaves"], g_sts_o):
            nd._data = leaf
        for nd, leaf in zip(d_host["nd_leaves"], d_sts_o):
            nd._data = leaf
        self.mod_g._params_dirty = True
        self.mod_d._params_dirty = True
        return WindowBoundary(n_steps, list(last))

    # ------------------------------------------------------------------
    def _serial_window(self, batches, latents):
        """Reference imperative loop (example/gan/dcgan.py ordering) — the
        fallback when the step cannot fuse, and the parity baseline the
        fused program is tested against."""
        from .. import ndarray as nd

        bs = self.batch_size
        mod_g, mod_d = self.mod_g, self.mod_d
        outs = None
        for i, b in enumerate(batches):
            real = b.data[0] if isinstance(b, DataBatch) else b
            if not isinstance(real, NDArray):
                real = nd.array(real)
            if latents is not None:
                noise = latents[i]
                if not isinstance(noise, NDArray):
                    noise = nd.array(noise)
            else:
                noise = nd.random_normal(
                    loc=0, scale=1, shape=(bs,) + self.code_shape)
            mod_g.forward(DataBatch(data=[noise], label=None), is_train=True)
            fake = mod_g.get_outputs()[0]

            mod_d.forward(DataBatch(data=[fake], label=[nd.zeros((bs,))]),
                          is_train=True)
            mod_d.backward()
            grads_fake = [[g.copy() if g is not None else None for g in gl]
                          for gl in mod_d._exec_group.grad_arrays]
            mod_d.forward(DataBatch(data=[real], label=[nd.ones((bs,))]),
                          is_train=True)
            mod_d.backward()
            for gl, gf in zip(mod_d._exec_group.grad_arrays, grads_fake):
                if gl[0] is not None:
                    gl[0] += gf[0]
            mod_d.update()
            # snapshot VALUES: the third forward below reuses the output
            # handles, so holding them would read the fake/1 pass instead
            outs = [o._data for o in mod_d.get_outputs()]

            mod_d.forward(DataBatch(data=[fake], label=[nd.ones((bs,))]),
                          is_train=True)
            mod_d.backward()
            diff_d = mod_d.get_input_grads()
            mod_g.backward(diff_d)
            mod_g.update()
        return WindowBoundary(len(batches), outs)
