"""Point-to-point sends through ``CommSession.send``, as OSU's ``osu_bw``
and ``osu_latency`` drive a pair of devices.

One caller, one send in flight (a window of 1, where ``osu_bw`` keeps 64):
each send starts from a message committed to ``src``, where its caller
holds it, and ends when the received array is ready on ``dst``. Messages
cycle through a pool of distinct seeded messages. A sample of the answers,
drawn from the seed, is compared after the window with a plain single-pair
``lax.ppermute`` of the same message, bit for bit on ``dst``. Every answer
must also be a buffer of its own: the answers of the last pool's worth of
sends are held, and one that shares a buffer with any of them is an
earlier answer handed back, whose bits the comparison would pass.

Traffic parameters (``params`` of the workload file): ``src``, ``dst``,
``nbytes`` (a whole number of elements of the configuration's dtype),
``messages`` (the pool), ``sample`` (answers compared) and ``warmup``
(sends before the window).
"""

from __future__ import annotations

from collections import Counter, deque

import numpy as np

import harness
import reference

#: Both are the window over the sends completed in it. A cell reports
#: ``send_ms`` where the device paces its sends, under a bound near its
#: own spread, and ``step_ms`` where the host does.
END_TO_END = {"send_ms": "ms", "step_ms": "ms"}


class Run:
    def __init__(self, s):
        import jax
        from jax.sharding import Mesh

        from repro.comm import CommConfig, CommSession

        cfg, p = s.cell.config, s.cell.params
        if cfg["dtype"] != "float32":
            raise harness.BenchError("p2p carries float32 messages")
        self.src, self.dst = int(p["src"]), int(p["dst"])
        self.nbytes = int(p["nbytes"])
        self.nelems = self.nbytes // 4
        self.count = int(p["messages"])
        self.window = int(cfg["window"])
        self.seed = s.seed
        self.devices = list(s.devices)
        self.session = CommSession(
            CommConfig(), mesh=Mesh(np.array(self.devices), ("dev",)))
        self.mesh, self.axis = self.session.mesh, self.session.axis_name
        src_dev = self.devices[self.src]
        stacked = reference.stacked_messages(
            s.seed, self.mesh, self.axis, self.count, self.nelems)
        if s.control:
            # The reference in the program's place, carried as bfloat16.
            self._stacked = [stacked[:, i] for i in range(self.count)]
            self._op = self._control_op
        else:
            block = reference.local_row(stacked, src_dev)
            self.messages = [block[i] for i in range(self.count)]
            del block
            self._op = self._send
        del stacked
        self.samples = harness.Reservoir(int(p["sample"]), s.seed)
        self.n = 0
        self.stale = 0
        #: (answer, its buffers) of the last ``count`` sends, and how many
        #: of them hold each buffer.
        self._recent, self._held = deque(), Counter()
        for i in range(int(p["warmup"])):
            jax.block_until_ready(self._op(i))

    def _send(self, i):
        return self.session.send(self.messages[i % self.count], self.src,
                                 self.dst, window=self.window)

    def _control_op(self, i):
        import jax.numpy as jnp
        y = reference.ppermute_reference(
            self.mesh, self.axis, self._stacked[i % self.count],
            [(self.src, self.dst)], dtype=jnp.bfloat16)
        return reference.local_row(y, self.devices[self.dst])

    def start_window(self, traced: bool) -> None:
        """Telemetry goes on for the traced run only, after warm-up."""
        if traced and not hasattr(self, "_stacked"):
            self.session.telemetry.clear()
            self.session.telemetry.enabled = True

    def call(self):
        return self._op(self.n)

    def done(self, out) -> None:
        bufs = {s.data.unsafe_buffer_pointer()
                for s in out.addressable_shards}
        self.stale += any(self._held[b] for b in bufs)
        if len(self._recent) == self.count:
            self._held.subtract(self._recent.popleft()[1])
        self._recent.append((out, bufs))
        self._held.update(bufs)
        self.samples.offer(out)
        self.n += 1

    def end_to_end(self, window_s: float, lat_ns: list) -> dict[str, float]:
        """The window over the sends completed in it, each waited on until
        the received array is ready on ``dst``: the one-way time of a send
        with one in flight. It is not ``osu_bw``'s bandwidth, which keeps
        64 sends in flight."""
        ms = window_s / len(lat_ns) * 1e3
        return {"send_ms": ms, "step_ms": ms}

    def layer_inputs(self) -> dict:
        tel = self.session.telemetry
        return {"nbytes": self.nbytes,
                "telemetry": tel.samples() if tel.enabled else ()}

    def release(self) -> None:
        """Free the program's state; the sampled answers stay."""
        for attr in ("messages", "_stacked", "session", "_recent", "_held"):
            self.__dict__.pop(attr, None)

    def check(self) -> tuple[list[harness.Check], int]:
        stacked = reference.stacked_messages(
            self.seed, self.mesh, self.axis, self.count, self.nelems)
        want_all = reference.local_row(
            reference.ppermute_reference(self.mesh, self.axis, stacked,
                                         [(self.src, self.dst)]),
            self.devices[self.dst])
        del stacked
        dst_dev = self.devices[self.dst]
        wants = [want_all[j] for j in range(self.count)]
        worst, failed = 0, 0
        for i, out in self.samples.items:
            got = reference.on_device(out, dst_dev)
            bad = reference.mismatched_elements(got, wants[i % self.count])
            worst, failed = max(worst, bad), failed + (bad > 0)
        return [harness.Check("mismatched_elems", float(worst), 0.0),
                harness.Check("stale_answers", float(self.stale), 0.0)
                ], failed + self.stale


def setup(s) -> Run:
    return Run(s)
