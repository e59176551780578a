"""Test-only architecture: the default decoder with a squared-ReLU MLP,
down(relu(up x)^2) with no gate, as the program computes
`mlp_kind: relu2`. It is resolved by name (`chipbench/arch.py`) and
brings its own weights, reference layer and counts."""
import functools

import jax
import jax.numpy as jnp

from chipbench import flops, reference, weights


def program_params(cj: dict, seed: int):
    """The default tree without the MLP's gate; up and down are the same
    draws as `weights.layer_weights` gives the reference."""
    params = weights.program_params(cj, seed)
    for block in params["blocks"]:
        block["mlp"].pop("gate")
    return params


@functools.partial(jax.jit, static_argnames=("dm",))
def _block(x, p, dm):
    m = dict(dm)
    x = reference.attend(x, p, m)
    h = reference._rms(x, m["eps"])
    y = jnp.square(jax.nn.relu(h @ p["up"])) @ p["down"]
    return x + m["m_residual"] * y


def served_gaps(cj, seed, seqs, prompt_lens, length):
    return reference.served_gaps(cj, seed, seqs, prompt_lens, length,
                                 block=_block)


class Shape(flops.Shape):
    def layer_params(self) -> int:
        return self.d * self.hd * (2 * self.hq + 2 * self.hkv) \
            + 2 * self.d * self.ff


def shape(cj: dict) -> Shape:
    return Shape.of(cj)
