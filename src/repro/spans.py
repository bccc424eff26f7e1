"""Host spans of the storage verbs, on the profiler's clock (leaf module).

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: inside a
profiler session it writes a host event into the same trace as the device
planes, so a device's idle gaps can be set against what the host was
doing; outside one it costs about a microsecond. The keyword ``args``
become the event's stats, which the profile viewer shows beside the span;
they are counters for operators, not inputs to any computation.

Every span the program emits: name, site, args.

* ``manifest``: ``archive.get_manifest`` and ``archive._put_manifest``.
* ``hot_load``: ``archive._hot_load_ex`` (store calls, digests, row
  copies) and ``archive._hot_rows`` (store calls, digests); ``bytes``.
* ``sha256``: ``object_store.digest``, which every whole digest goes
  through, and the stripe body's incremental block digests; ``bytes``.
* ``host_copy``: host-side copies of payload, where the payload must sit
  in one host array: hot rows into the object (``_hot_load_ex``), rows
  gathered for a sub-packetized code or a device mesh (``_gather``), the
  sub-packetized message (``_fused_encode``), range slices and decoded
  bytes (``read_range_ex``); ``bytes``. The fused archive and repair of a
  positionwise code copy nothing: coded and repaired rows go to the store
  as views.
* ``h2d``: the coding kernel's input sent to the device, one span per
  launch (``_to_device``, or ``_fused_encode``'s ``jnp.asarray`` of a
  sub-packetized message) and one per stripe (``streaming.execute``);
  ``bytes`` in all, and ``direct``, the store buffers sent as they are,
  with no host copy (k per archived object or archive stripe, the helpers
  per repaired one; 0 for a sub-packetized message or a stripe assembled
  on the host).
* ``kernel_launch``: the coding kernel's dispatch with its pack and unpack,
  per launch or per stripe; host side, it returns before the device ends;
  ``kernel``. The fused archive's (``_fused_encode``) also splits the
  result into one device array per coded row and starts each row's copy
  back; its ``streamed`` counts those rows (O * R).
* ``d2h``: ``np.asarray`` of a kernel result, which waits for the device
  and the copy back; ``bytes``, only the rows copied back. One per repair
  launch and one per stripe (whose copy back was started when it was
  dispatched). In the fused archive one per coded row, taken as
  ``archive._put_coded`` writes it, so the writes and hashes of the rows
  before it overlap its copy; ``row``, its codeword row. A fused archive
  codes only the generator rows that are not unit rows, so a systematic
  code's data rows never cross: the launch's first ``d2h`` carries
  ``passthrough``, the rows written from the verified hot buffers
  instead (k per object for LRC and Xorbas, 0 for RapidRAID and MBR).
* ``stripe_read``: one per stripe in the one stripe body,
  ``archive._archive_step_streaming``, which codes a streamed
  ``archive_step`` and ``publish_streaming_archive``: the stripe's reads
  (hot range reads, or slices of the in-memory blocks) and their
  incremental digests (under ``sha256``); ``stripe``, ``bytes``.
* ``stripe_write``: one per stripe in the same body: the stripe's framed
  coded writes and their per-stripe digests; ``stripe``, ``bytes`` (all n
  rows), ``passthrough`` (the rows written from the stripe's own reads,
  as ``d2h``'s).
* ``reclaim``: the hot-replica deletes in ``archive._publish``, which
  every archive of a hot step ends in (``archive_step``,
  ``archive_many``, whole or in stripes).
* ``repair_plan``: ``code.repair_helpers`` (``_repair_state``) and
  ``fault_tolerance.repair_plan`` (``repair_many``); ``helpers``, the
  rows the plan reads.
* ``place_repaired``: ``archive._place_repaired``.
* ``read_plan``: ``read_range_ex``'s alive probe, helper choice and decode
  matrix.
* ``read_decode``: ``gf_matmul_np`` per touched block (``read_range_ex``);
  ``bytes``.
* ``store.<call>``: ``NodeStore.put``, ``get``, ``get_range``, ``has``,
  ``delete``, ``put_stream`` (opening, each write, the publish) and
  ``get_stream`` (each frame); ``bytes`` where known.

No span is named after a client verb (``archive``, ``repair``,
``read_range``): a caller that times the verbs marks them under those
names. No span nests inside another of its own name, except a
``store.<call>`` inside a store subclass's own ``store.<call>``.
"""
from __future__ import annotations

import jax


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` with ``args`` as its stats; use as ``with``."""
    return jax.profiler.TraceAnnotation(name, **args)
