"""Job kind ``train_lm``: ``train_fit`` for a language model too large to
hold the harness's float32 copies beside its step.

It is ``train_fit.Job`` — the same one ``fit`` call, phases, readings and
judgement — with two things added:

* the feed kind ``token_ids``: int32 rows drawn from the seed by a Zipf law
  over EVERY id of the vocabulary (``datagen.make_tokens`` draws only the
  ids a bfloat16 float holds exactly; the program now keeps ids integer
  from the iterator to the step);
* the copies a comparison needs are held on the host while nothing works
  on them: the seeded weights, the program's first gradient and change,
  and the reference's.  The reference is followed one ROW at a time
  (``reference/<name>.py: row_loss``), its gradients accumulated, with
  only weights, momentum and gradient on the device beside one row's
  activations — ``common.follow`` holds five float32 copies of the
  parameters (12.5 GB at 626 M), which a 16 GB chip cannot.  The readings
  are the same: ``common.sgd_momentum`` is still the rule.
"""
import numpy as np

from benchmark import compare, datagen
from benchmark.jobs import train_fit
from benchmark.jobs.train_fit import CHECK_STEPS


def zipf_tokens(seed, rows, seq_len, vocab, exponent):
    """(data, label) int32 (rows, seq_len): ids drawn from the seed with
    P(id) ~ 1 / (id + 1)^exponent over all ``vocab`` ids; label is the next
    token of the same row."""
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    cdf = np.cumsum(p / p.sum())
    u = datagen.np_rng(seed, 2).random_sample((rows, seq_len + 1))
    toks = np.minimum(np.searchsorted(cdf, u), vocab - 1).astype(np.int32)
    return toks[:, :-1].copy(), toks[:, 1:].copy()


class Job(train_fit.Job):
    def setup(self):
        import jax
        super().setup()
        self.w0 = jax.device_get(self.w0)     # the step needs the room

    def _feed_token_ids(self, mx):
        t = self.traffic
        data, label = zipf_tokens(
            self.seed, int(t["batches"]) * self.batch,
            int(self.model["seq_len"]), int(self.model["vocab_size"]),
            float(t["zipf_exponent"]))
        return mx.io.NDArrayIter(data, label, batch_size=self.batch)

    # -- readings of the program's first steps, kept on the host -------------
    def after_check_step(self, k):
        import jax
        import jax.numpy as jnp
        from benchmark.reference.common import leaf_norms

        if k == 1:
            lr = float(self.opt["learning_rate"])
            mom = self.ref.from_program(
                {n: s[0] for n, s in self.trainer.opt_state.items()},
                self.model)
            grad1 = jax.jit(lambda m: {n: v / -lr for n, v in m.items()})(
                mom)
            self._grad1 = jax.device_get((jax.jit(leaf_norms)(grad1), grad1))
        if k == CHECK_STEPS:
            now = self.ref.from_program(dict(self.trainer.params),
                                        self.model)
            change = jax.jit(lambda a, b: {
                n: a[n] - b[n].astype(jnp.float32) for n in b})(now, self.w0)
            norms, change = jax.device_get((jax.jit(leaf_norms)(change),
                                            change))
            self.program = {
                "grad1": {n: float(v) for n, v in self._grad1[0].items()},
                "change": {n: float(v) for n, v in norms.items()},
                "full": {"grad1": self._grad1[1], "change": change}}
            self.w0 = self._grad1 = None

    # -- the reference, one row at a time -------------------------------------
    def _follow(self, precision, batches, frozen=False):
        """``common.follow``'s readings from ``row_loss``.  ``frozen``: the
        state is handed back unchanged after every step (the planted
        fault): no gradient reaches the optimizer, nothing moves."""
        import jax
        import jax.numpy as jnp
        from benchmark.reference import common

        rows = self.batch
        row_grad, accumulate, update = _cached_fns(
            self.ref, self.model, self.opt, rows, precision)
        init = jax.jit(lambda k: self.ref.init(k, self.model)[0])
        key = datagen.jax_key(self.seed, 3)
        params = init(key)
        mom = jax.tree.map(jnp.zeros_like, params)
        norms = jax.jit(common.leaf_norms)
        losses, first, raw, seen1 = [], None, None, None
        for i, batch in enumerate(batches):
            loss, grads = 0.0, None
            for r in range(rows):
                row, g = row_grad(params, batch["data"][r],
                                  batch["softmax_label"][r])
                grads = g if grads is None else accumulate(grads, g)
                loss = loss + row
            losses.append(loss / rows)
            if i == 0:
                # the gradient as the rule hands it to the update (the
                # momentum it is given does not enter that)
                seen = jax.jit(lambda p, g: common.sgd_momentum(
                    p, g, g, self.opt, rows)[2])(params, grads)
                if frozen:
                    seen = jax.tree.map(jnp.zeros_like, seen)
                first, raw, seen1 = jax.device_get(
                    (norms(seen), norms(grads), seen))
                del seen
            if not frozen:
                params, mom = update(params, mom, grads)
            del grads
        change = jax.jit(lambda a, b: {k: a[k] - b[k] for k in a})(
            params, init(key))
        del params, mom
        change_norms, change = jax.device_get((norms(change), change))
        return {"loss": [float(x) for x in jax.device_get(losses)],
                "grad1": {k: float(v) for k, v in first.items()},
                "grad1_raw": {k: float(v) for k, v in raw.items()},
                "change": {k: float(v) for k, v in change_norms.items()},
                "full": {"grad1": seen1, "change": change}}

    def compare(self, precision="f32", batches=None, wrap=None):
        """As ``train_fit.Job.compare``.  ``wrap`` is the limits tool's one
        planted fault in the step, ``state_unchanged``; it is honoured by
        name, since a wrapper that hands donated buffers back cannot work
        on a step that has no room for a second copy of its state."""
        from benchmark.reference import common

        if wrap is not None and wrap.__name__ != "state_unchanged":
            raise ValueError("train_lm knows the fault state_unchanged, "
                             "not %r" % wrap.__name__)
        batches = self.check_batches() if batches is None else batches
        readings = self._follow(precision, batches, frozen=wrap is not None)
        if precision != "f32":
            return readings
        self.reference = readings
        self.program = common.differences(self.program, readings)
        gaps = compare.training_gaps(self.program, readings)
        return compare.judge(gaps, self.limits)


_FNS = {}


def _cached_fns(ref, model, opt, rows, precision):
    """(row_grad, accumulate, update), jitted once per (configuration,
    precision) and process."""
    import json

    import jax
    from benchmark.reference import common

    key = (ref.__name__, json.dumps(model, sort_keys=True),
           json.dumps(opt, sort_keys=True), rows, precision)
    if key not in _FNS:
        def update(params, mom, grads):
            return common.sgd_momentum(params, grads, mom, opt, rows)[:2]

        _FNS[key] = (
            jax.jit(jax.value_and_grad(ref.row_loss(model, precision))),
            jax.jit(lambda a, b: jax.tree.map(lambda x, y: x + y, a, b),
                    donate_argnums=(0,)),
            jax.jit(update, donate_argnums=(0, 1)))
    return _FNS[key]
