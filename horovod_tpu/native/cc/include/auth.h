// Connection authentication for the control and data planes.
//
// Reference equivalent: horovod/run/common/network.py:50-84 — the
// launcher's RPC wire HMAC-signs every message with a per-job secret so
// arbitrary processes cannot inject commands.  Here the same trust
// boundary exists at the controller rendezvous and the data-plane mesh:
// without auth, any process that can reach the port can claim a rank
// (an early review finding).  The handshake is mutual challenge-response
// with HMAC-SHA256 over fresh nonces, run once per connection at connect
// time; after it succeeds the connection is trusted.
//
//   acceptor                      connector
//     nonce_a (32B frame)  ---->
//                          <----  nonce_c || HMAC(key, "hvd-client" |
//                                                nonce_a | nonce_c)
//     HMAC(key, "hvd-server" |
//          nonce_c | nonce_a) -->
//
// The role strings prevent reflection (echoing a side's own MAC back).
// Key source: HOROVOD_SECRET_KEY (urlsafe base64, set per-job by the
// hvdrun launcher).  When unset, the handshake is skipped entirely —
// single-process usage and hand-launched jobs keep working; the launcher
// always sets it.
#ifndef HVD_AUTH_H
#define HVD_AUTH_H

#include <cstdint>
#include <string>

#include "hvd_common.h"
#include "socket.h"

namespace hvd {

// SHA-256 (FIPS 180-4) of `data`; returns 32 raw bytes.
std::string Sha256(const void* data, size_t n);

// HMAC-SHA256 (RFC 2104) of `msg` under `key`; returns 32 raw bytes.
std::string HmacSha256(const std::string& key, const std::string& msg);

// Constant-time equality (length leak is fine — lengths are public).
bool ConstantTimeEq(const std::string& a, const std::string& b);

// 32 bytes from /dev/urandom (falls back to std::random_device).
std::string RandomNonce();

// Per-job secret from HOROVOD_SECRET_KEY (urlsafe base64; tolerates raw
// strings that fail to decode).  Empty string = auth disabled.
std::string JobKey();

// Run the acceptor side of the handshake on a fresh connection.  With an
// empty key this is a no-op returning OK.  A failure means the peer did
// not prove knowledge of the key — the caller should close the socket and
// keep accepting (robustness against port scanners), not abort the job.
Status AuthAccept(const TcpSocket& sock, const std::string& key);

// Connector side.  With an empty key this is a no-op returning OK.
Status AuthConnect(const TcpSocket& sock, const std::string& key);

}  // namespace hvd

#endif  // HVD_AUTH_H
