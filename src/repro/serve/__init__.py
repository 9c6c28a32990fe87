"""Serving the merged model u_k: offline generation and an online engine.

The hierarchy trains per-worker replicas, but what a deployment runs is the
weighted average u_k = X a (hubs are stateless per the paper) — everything
in this package operates on that single merged parameter set.

Two serving paths share the model code in `repro.models`:

* `serve_step` — offline/sequential: ``generate`` prefills a prompt (one
  batched forward for attention-only models, a per-token loop otherwise —
  the loop is kept as the any-architecture parity oracle) and then decodes
  against the rotating-buffer dense KV cache.  This is also what the
  decode-shape dry-runs lower.
* `engine` — online continuous batching: ``ServeEngine`` multiplexes many
  requests over a fixed pool of decode lanes.

**Phases** (engine): each engine step is one *slot*.  A slot either
prefills the batch of newly admitted requests (one forward pass captures
every layer's k/v and samples each request's first token) or advances all
active lanes by one token.  Admission is FIFO and all-or-nothing on cache
blocks; finished requests free their blocks immediately for reuse.

**Cache layout** (`kv_cache`): per attention layer, one shared pool of
``num_blocks`` fixed-size blocks, shape (num_blocks, Hkv, block_size, hd).
A request's context is a row of the (max_batch, max_blocks) block table;
logical position p lives at ``pool[table[lane, p // bs], :, p % bs]``.
Decode reads the table either through an XLA gather (`gather_kv` + masked
SDPA, the oracle) or the Pallas flash-decode kernel
(`kernels.ops.flash_decode`: split-KV grid, in-kernel block-table
indirection via scalar prefetch, per-split logsumexp combine).

**Trace schema**: `ServeEngine.trace` emits the same
``mll-timeline-trace/v1`` document the training timeline exports — one
slot per engine step, busy/idle lane counts per slot, one round per
finished request — with per-request latency records (admission,
first-token and finish slots + wall-clock TTFT/latency) under
``meta["requests"]``.  `core.timeline.load_trace` reads both.
"""
