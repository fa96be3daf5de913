"""What the model builders share: handing seeded weights to a Gluon net
through its own initializer hook, the ring of seeded batches, and the
trainer every training cell runs."""

from __future__ import annotations

import numpy as np


def seeded_net(net, table, values):
    """Initialize *net* with *values* (the reference's ``name -> array``,
    made from the seed) through Gluon's initializer protocol.  *table* is
    the reference's ordered ``name -> (shape, init)``; the program's
    trained parameters are matched to it in order, by shape.  Returns the
    ``reference name -> program name`` map."""
    import mxnet_tpu as mx

    params = net.collect_params()
    # ParallelTrainer keeps its own gradients inside the compiled step;
    # Gluon's per-parameter gradient buffers would be a second float32
    # copy of the model on the default device
    params.setattr("grad_req", "null")
    trained = [p for p in params.values()
               if not p.name.endswith(("running_mean", "running_var"))]
    if len(trained) != len(table):
        raise RuntimeError("the program has %d trained parameters, the "
                           "reference %d" % (len(trained), len(table)))
    names = {}

    class Seeded(mx.init.Initializer):
        def __init__(self, value):
            super().__init__()
            self.value = value

        def __call__(self, desc, arr):
            arr._data = self.value

    for p, (ref_name, (shape, _)) in zip(trained, table.items()):
        want = tuple(p.shape or ())
        if len(want) != len(shape) or any(
                w not in (0, s) for w, s in zip(want, shape)):
            raise RuntimeError("parameter %s %r does not match the "
                               "reference's %s %r"
                               % (p.name, want, ref_name, shape))
        p.shape = shape
        # the parameter's own `init` would win over a net-wide one
        p.initialize(init=Seeded(values[ref_name]))
        names[ref_name] = p.name
    net.initialize()  # what is left: running statistics, deferred
    return names


class RingIter:
    """The DataIter protocol over a ring of numpy batches, cycled with no
    epoch boundary: what a job sees in the middle of an epoch."""

    def __init__(self, batches):
        from mxnet_tpu.io import DataBatch
        self._batches = [DataBatch(data=[x], label=[y]) for x, y in batches]
        self.batch_size = int(batches[0][0].shape[0])
        self._i = 0

    provide_data = provide_label = None

    def __iter__(self):
        return self

    def next(self):
        b = self._batches[self._i % len(self._batches)]
        self._i += 1
        return b

    __next__ = next

    def reset(self):
        self._i = 0


def make_trainer(net, loss, train, devices):
    """The `ParallelTrainer` of a configuration's `train` section over
    *devices*."""
    from mxnet_tpu.parallel.data_parallel import ParallelTrainer
    from mxnet_tpu.parallel.mesh import make_mesh

    return ParallelTrainer(
        net, loss, optimizer=train["optimizer"],
        optimizer_params={"learning_rate": train["lr"],
                          "momentum": train["momentum"],
                          "wd": train["wd"]},
        mesh=make_mesh({"dp": len(devices)}, list(devices)),
        multi_precision=train["multi_precision"],
        remat=train.get("remat"))


def rng_for(seed, stream):
    return np.random.default_rng([int(seed), int(stream)])
