"""UnifyFL core: the paper's contribution.

store       -- content-addressed distributed storage (IPFS analogue)
ledger      -- PoA hash-chained log: single-replica facade over repro.chain
               (the genuinely replicated Clique chain over the WAN fabric)
contract    -- the UnifyFL smart contract (paper Algorithm 1)
scoring     -- accuracy / loss / MultiKRUM scorers (paper §2.6)
policies    -- aggregation + score policies (paper §3.4.4)
orchestrator-- Sync / Async round engines with straggler & failure handling
exchange    -- jittable cross-silo exchange over the 'pod' mesh axis
wire        -- the one model-exchange codec (versioned ModelEnvelope:
               raw | int8 | int8-delta | topk-delta, base-chain resolution)
"""
