"""Family `resnet_gluon`: a model-zoo ResNet v1 (bottleneck blocks) trained
through `mxnet_tpu.parallel.TrainStep`, the fused step a user calls.

The benchmark makes the leaves and the resident batches on the device from
the seed, in the order and layout of `chipbench/reference/resnet_gluon.py`,
and sets the leaves into the Gluon net. Set-up drives the one compiled step
through its first steps and keeps what the check needs (the momentum after
step one, the leaves after the last); the window then goes on with the same
object. After the window the reference follows the same steps from leaves
and batches made again from the seed.

Where the traffic names a mesh, leaves are replicated by `TrainStep` and the
resident batches are placed over the mesh by their rows before the window, as
a sharded input pipeline leaves them: the step's own `shard_batch` then finds
them where it wants them and moves nothing. `train_flops_per_step` is the
numerator of `mxu_share.train`, from shapes alone.
"""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness import util
from chipbench.reference import resnet_gluon as reference


def make_leaves(config, seed):
    """Trained leaves in `reference.leaf_spec` order, float32, one jitted
    call: He-normal convolutions and dense weight, gains near 1, biases and
    offsets near 0 (none exactly, so a dropped term shows)."""
    spec = reference.leaf_spec(config)

    @jax.jit
    def make(key):
        out = []
        for (name, shape), k in zip(spec, jax.random.split(key, len(spec))):
            if name.endswith(".w"):
                fan_in = int(np.prod(shape[1:]))
                out.append(jnp.sqrt(2.0 / fan_in) * jax.random.normal(k, shape))
            elif name.endswith(".g"):
                out.append(1.0 + 0.1 * jax.random.normal(k, shape))
            else:
                out.append(0.05 * jax.random.normal(k, shape))
        return out

    return make(jax.random.fold_in(util.prng_key(seed), 1))


def make_batches(config, traffic, seed):
    """`resident_batches` batches of images and labels on the device, every
    row different: ([x (B,3,H,W) float32], [y (B,) int32])."""
    n, batch, image = (traffic["resident_batches"], traffic["batch"],
                       config["image"])

    @jax.jit
    def make(key):
        kx, ky = jax.random.split(key)
        x = jax.random.normal(kx, (n, batch, 3, image, image), jnp.float32)
        y = jax.random.randint(ky, (n, batch), 0, config["classes"], jnp.int32)
        return [x[i] for i in range(n)], [y[i] for i in range(n)]

    return make(jax.random.fold_in(util.prng_key(seed), 2))


def forward_flops_per_image(config):
    """Multiply-adds x 2 of every convolution and the dense layer of a
    bottleneck ResNet v1 as Gluon builds it (stride on the first 1x1)."""
    size = config["image"] // 2                       # 7x7 stride 2
    flops = 2 * 49 * 3 * config["channels"][0] * size * size
    size //= 2                                        # max pool
    cin = config["channels"][0]
    for s, (n, cout) in enumerate(zip(config["layers"], config["channels"][1:])):
        mid = cout // 4
        for b in range(n):
            if b == 0 and s > 0:
                size //= 2
            macs = (cin * mid + 9 * mid * mid + mid * cout) * size * size
            if b == 0 and cin != cout:
                macs += cin * cout * size * size
            flops += 2 * macs
            cin = cout
    return flops + 2 * cin * config["classes"]


def train_flops_per_step(config, global_batch):
    """Forward and backward over the global batch: the backward pass computes
    a gradient for the input and one for the weight of every layer, twice the
    forward's work."""
    return 3 * forward_flops_per_image(config) * global_batch


class Trainer:
    trace_slice_s = 2.5       # about 25 steps: traces are large

    def __init__(self, cell):
        from mxnet_tpu.gluon import loss as gloss
        from mxnet_tpu.gluon.model_zoo.vision import resnet as zoo
        from mxnet_tpu.ndarray import NDArray
        from mxnet_tpu.parallel import TrainStep
        cfg, traffic = cell.config, cell.traffic
        self.cell = cell
        self.check_steps = traffic["check_steps"]
        self.batch = traffic["batch"]
        net = zoo.ResNetV1(zoo.BottleneckV1, cfg["layers"], cfg["channels"],
                           classes=cfg["classes"])
        leaves = iter(make_leaves(cfg, cell.seed))
        width = None
        for name, p in net.collect_params().items():
            if p.grad_req == "null":
                # running statistics of the batch norm whose gain and offset
                # came just before; the training-mode forward never reads them
                fill = jnp.ones if name.endswith("running_var") else jnp.zeros
                p.set_data(NDArray(fill((width,), jnp.float32)))
            else:
                leaf = next(leaves)
                width = leaf.shape[0]
                p.set_data(NDArray(leaf))
        mesh = None
        if traffic.get("mesh"):
            from mxnet_tpu.parallel.mesh import build_mesh
            mesh = build_mesh(dict(traffic["mesh"]),
                              jax.devices()[:cell.chips])
        self.mesh = mesh
        self.optimizer = dict(cfg["trainer"]["optimizer_params"])
        self._step = TrainStep(
            net, gloss.SoftmaxCrossEntropyLoss(), cfg["trainer"]["optimizer"],
            dict(self.optimizer), dtype=cfg["trainer"]["dtype"],
            mesh=mesh)
        self.xs, self.ys = self._place(make_batches(cfg, traffic, cell.seed))
        self.n_steps = 0
        self.last_loss = None
        self.first = {}

    def _place(self, batches):
        """Batches where the step wants them: as made on one chip, by their
        rows over the mesh's first axis on several."""
        if self.mesh is None:
            return batches
        from jax.sharding import NamedSharding, PartitionSpec as P
        rows = NamedSharding(self.mesh, P(self.mesh.axis_names[0]))
        return jax.block_until_ready(jax.device_put(batches, rows))

    # -- what the generator drives -------------------------------------------

    def step(self):
        """Dispatch one optimizer step on the next resident batch; returns
        the loss as a device scalar without waiting for it."""
        i = self.n_steps % len(self.xs)
        self.n_steps += 1
        self.last_loss = self._step(self.xs[i], self.ys[i])
        return self.last_loss

    def block(self):
        """Wait for the last step's loss and parameters."""
        jax.block_until_ready((self.last_loss, self._live("grad_vals")))

    def _live(self, part):
        return self._step.state_dict(device=True)[part]

    def first_steps(self):
        """The first `check_steps` steps, through `step` like every later
        one, keeping copies (the step donates its buffers) of the momentum
        after the first and of the leaves after the last."""
        losses = []
        for i in range(self.check_steps):
            losses.append(self.step())
            if i == 0:
                self.first["momentum"] = [jnp.copy(s[0])
                                          for s in self._live("opt_state")]
        self.first["leaves"] = [jnp.copy(w) for w in self._live("grad_vals")]
        self.first["losses"] = [float(v) for v in losses]

    # -- after the window ----------------------------------------------------

    def counters(self):
        homes = {d for v in self._live("grad_vals") for d in v.devices()}
        return {"param_devices": len(homes), "steps": self.n_steps,
                "global_batch": self.batch}

    def close(self):
        pass

    def host_spans(self, record, spans):
        """What the host was doing, for labelling the device's idle gaps:
        the generator's dispatch calls (name, start, end; perf_counter s)."""
        return [("train.dispatch", record["t0"] + at, record["t0"] + at + ms / 1e3)
                for at, ms in record.get("dispatch", [])]

    def _reference(self, mantissa_bits=None):
        cfg = self.cell.config
        start = make_leaves(cfg, self.cell.seed)
        xs, ys = self._place(make_batches(cfg, self.cell.traffic,
                                          self.cell.seed))
        if self.mesh is not None:
            # the reference's float32 activations of a global batch do not fit
            # one chip: its rows are spread over the cell's chips as the
            # program's are (the reference itself is the same plain code)
            from jax.sharding import NamedSharding, PartitionSpec as P
            start = jax.device_put(start, NamedSharding(self.mesh, P()))
        followed = reference.follow(
            start, list(zip(xs, ys))[:self.check_steps], cfg, self.optimizer,
            mantissa_bits=mantissa_bits)
        return start, followed

    def _compare(self, losses, grad_norms, change_norms, ref):
        limits = self.cell.config["check"]
        ref_losses, ref_grad, ref_change = ref
        out = [util.compared("loss_step%d_gap" % (i + 1), abs(got - float(want)),
                             limits["loss_gap"])
               for i, (got, want) in enumerate(zip(losses,
                                                   np.asarray(ref_losses)))]
        names = [n for n, _ in reference.leaf_spec(self.cell.config)]
        for what, got, want in (("first_grad_norm", grad_norms, ref_grad),
                                ("param_change_norm", change_norms,
                                 ref_change)):
            gaps = np.asarray(reference.leaf_gaps(got, want))
            out.append(dict(util.compared(what + "_gap", gaps.max(),
                                          limits[what + "_gap"]),
                            worst_leaf=names[int(gaps.argmax())]))
            out.append(util.compared(what + "_gap_median_leaf",
                                     float(np.median(gaps)),
                                     limits[what + "_gap_median_leaf"]))
            out.append(util.compared(what + "_gap_all_leaves",
                                     reference.whole_gap(got, want),
                                     limits[what + "_gap_all_leaves"]))
        return out

    def check(self, record):
        """Training's comparison of "How correct is decided": each of the
        first steps' losses, the first gradient's norm as the optimizer got
        it (worked out from the momentum after step one), and the norm of
        the leaves' change after the steps, each by the worst leaf against
        the reference following the same steps."""
        start, ref = self._reference()
        grad_norms, change_norms = program_norms(
            self.first["momentum"], self.first["leaves"], start,
            self.optimizer["learning_rate"], self.optimizer["wd"])
        out = self._compare(self.first["losses"], grad_norms, change_norms, ref)
        out.append(util.compared(
            "window_losses_not_finite",
            sum(1 for v in record.get("losses", []) if not np.isfinite(v)), 0))
        out.append(util.compared(
            "params_off_the_cells_chips",
            abs(self.counters()["param_devices"] - self.cell.chips), 0))
        return out

    def control(self, record):
        """The reference in the program's place, computing in fp8's mantissa
        where the program computes in bfloat16's: the numbers it gives under
        the same comparison."""
        _, ref = self._reference()
        _, low = self._reference(
            mantissa_bits=self.cell.config["check"]["control_mantissa_bits"])
        return self._compare([float(v) for v in np.asarray(low[0])],
                             low[1], low[2], ref)


@jax.jit
def program_norms(momentum, leaves, start, lr, wd):
    """Per-leaf norms on the program's side: the first gradient as the
    optimizer got it, from m1 = -lr * (g + wd * w0), and the change of the
    leaves."""
    grads = [-m / lr - wd * w for m, w in zip(momentum, start)]
    norm = lambda t: jnp.sqrt(jnp.sum(jnp.square(t.astype(jnp.float32))))
    return (jnp.stack([norm(g) for g in grads]),
            jnp.stack([norm(w - a) for w, a in zip(leaves, start)]))


def build(cell):
    return Trainer(cell)
