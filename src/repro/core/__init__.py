# The paper's primary contribution: the decoupled (one-sided) MapReduce
# engine and its bulk-synchronous reference, behind the unified Job API —
# pluggable backends (registry), declarative use-cases, and a streaming
# JobHandle lifecycle.
from repro.core.job import (CombineOverflowError, JobConfig, JobHandle,
                            JobResult, submit)
from repro.core.partition import (HashPartitioner, Partitioner,
                                  SampledPartitioner,
                                  available_partitioners,
                                  resolve_partitioner)
from repro.core.registry import (Backend, JobSpec, UnknownBackendError,
                                 available_backends, get_backend,
                                 register_backend)
from repro.core.scheduler import (AdmissionQueueFull, FairSharePolicy,
                                  FifoPolicy, JobScheduler, PriorityPolicy,
                                  SchedulePolicy, TenantStats,
                                  available_policies, resolve_policy)
from repro.core.usecase import UseCase, as_map_fn
from repro.core.usecases import (Histogram, InvertedIndex, Postings,
                                 PostingsIndex, WordCount, histogram_oracle,
                                 inverted_index_oracle, wordcount_oracle)
from repro.core.workdomain import WorkDomain, can_coschedule
