"""Event-driven federation runtime (paper §3 made first-class).

  transport   links, byte accounting, compression codecs
  events      event queue + client availability traces
  aggregate   the server reduce: one interface over the decode / stream /
              batched modes (make_server_reduce)
  policies    FedAsync / FedBuff async aggregation
  programs    the client-side local round as data: one step definition
              (plain | DP-SGD), two compilations (loop | vectorized),
              per-client lr/steps schedules, pure round execution
  engine      discrete-event round engine (sync + async scheduling) that
              schedules programs
"""
from repro.fed.engine import (ClientSpec, FederationEngine,  # noqa: F401
                              RoundReport)
from repro.fed.events import (AlwaysAvailable,  # noqa: F401
                              BernoulliAvailability, EventQueue,
                              make_availability)
from repro.fed.policies import (AggregationPolicy, ClientUpdate,  # noqa: F401
                                FedAsync, FedBuff, make_policy)
from repro.fed.programs import (BACKENDS, CallableProgram,  # noqa: F401
                                ClientHyper, ClientResult, LocalProgram,
                                RoundExecutor, as_program, fedavg_stacked,
                                make_local_step, sequential_d_rounds,
                                stack_trees, unstack_tree)
from repro.fed.transport import (Codec, FP16Codec, IdentityCodec,  # noqa: F401
                                 Int8Codec, LinkModel, TopKCodec,
                                 TrafficLedger, apply_delta, delta_tree,
                                 fake_batch_bytes, make_codec, tree_bytes)
