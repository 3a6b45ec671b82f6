"""Faults planted in the program underneath a run, each of which a sound
check has to catch (``correct`` comes out false)."""
import contextlib

import jax.numpy as jnp


@contextlib.contextmanager
def patched(obj, name, fn):
    old = getattr(obj, name)
    setattr(obj, name, fn)
    try:
        yield
    finally:
        setattr(obj, name, old)


def answer_altered():
    """One twin's trajectory comes out of the solve 10% off."""
    from repro.core.backends import FusedPallasBackend
    solve = FusedPallasBackend._solve

    def bad(self, *a, **k):
        traj = solve(self, *a, **k)
        return traj.at[1:, 0].multiply(1.1)
    return patched(FusedPallasBackend, "_solve", bad)


def state_unchanged():
    """Served windows are not written back: each twin restarts from the
    state it had before."""
    from repro.launch.state_store import TwinStateStore
    commit = TwinStateStore.commit

    def bad(self, ids, ys, steps):
        hot = self._hot
        commit(self, ids, ys, steps)
        self._hot = hot
    return patched(TwinStateStore, "commit", bad)


def rows_mixed():
    """Half of a batch gets its neighbours' rows: batching mixes twins."""
    from repro.core.backends import FusedPallasBackend
    solve = FusedPallasBackend._solve

    def bad(self, state, y0s, *a, **k):
        n = y0s.shape[0] // 2
        y0s = jnp.concatenate([jnp.roll(y0s[:n], 1, axis=0), y0s[n:]])
        return solve(self, state, y0s, *a, **k)
    return patched(FusedPallasBackend, "_solve", bad)


def trajectory_frozen():
    """The solve returns its state unchanged: every row of a window is
    its first."""
    from repro.core.backends import FusedPallasBackend
    solve = FusedPallasBackend._solve

    def bad(self, *a, **k):
        traj = solve(self, *a, **k)
        return jnp.broadcast_to(traj[:1], traj.shape)
    return patched(FusedPallasBackend, "_solve", bad)


def exchange_dropped():
    """The output is never gathered from the twin mesh: every device's
    rows are the first device's."""
    from repro.launch.fleet_serving import FleetServer
    serve = FleetServer.serve

    def bad(self, *a, **k):
        out = serve(self, *a, **k)
        m = out.shape[0] // self.n_shards
        return jnp.concatenate([out[:m]] * self.n_shards
                               + [out[m * self.n_shards:]])
    return patched(FleetServer, "serve", bad)


def update_skipped():
    """The optimizer step returns the parameters unchanged."""
    from repro.train import trainer
    return patched(trainer, "apply_updates", lambda params, updates: params)


def half_batch():
    """The loss sees only the first half of the shooting segments."""
    from repro.train import trainer
    real = trainer.segment_loss_fn

    def bad(twin, ts_seg, ys_seg, *a, **k):
        n = ts_seg.shape[0] // 2
        return real(twin, ts_seg[:n], ys_seg[:n], *a, **k)
    return patched(trainer, "segment_loss_fn", bad)


FAULTS = {"answer_altered": answer_altered, "state_unchanged": state_unchanged,
          "rows_mixed": rows_mixed, "trajectory_frozen": trajectory_frozen,
          "exchange_dropped": exchange_dropped,
          "update_skipped": update_skipped, "half_batch": half_batch}
