"""Value-clustered keys: the paper's ``local`` array (§5), as
``repro.data.distributions.make_array("local")`` draws it from the stream
``rng`` (it hands a generator to ``np.random.default_rng`` unaltered)."""

from repro.data.distributions import make_array


def draw(rng, n, dtype):
    return make_array("local", n, seed=rng, dtype=dtype)
