"""A toy reference for the dispatch test: linear regression learned by
plain SGD, one update per window of k steps.  Parameters {"w": [d]};
inputs xs [windows*k, B, d], ys [windows*k, B]; a window's loss is the mean
squared error over its steps and examples."""
import jax
import jax.numpy as jnp

INPUTS = ("xs", "ys")


def make_reference(model, windows, matmul="highest"):
    k, lr = int(model["update_every"]), float(model["lr"])

    def window_loss(w, xs, ys):
        return jnp.mean((jnp.einsum("tbd,d->tb", xs, w) - ys) ** 2)

    def run(shared, stream):
        w = shared["params0"]["w"]
        losses, grad1 = [], None
        for i in range(windows):
            sl = slice(i * k, (i + 1) * k)
            loss, g = jax.value_and_grad(window_loss)(
                w, stream["xs"][sl], stream["ys"][sl])
            grad1 = {"w": g} if grad1 is None else grad1
            losses.append(loss)
            w = w - lr * g
        return {"loss": jnp.stack(losses), "grad1": grad1,
                "params": {"w": w}}
    return run
